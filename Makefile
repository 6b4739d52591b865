# Builder entry points (ISSUE 3 satellite: stop re-typing incantations).
#   make tier1   - the canonical tier-1 verify (scripts/run_tier1.sh)

.PHONY: tier1

tier1:
	scripts/run_tier1.sh
