"""Share of a speculative tick's rounds in which the verify pass ran:
``100 * rounds_verified / rounds`` summed over the ``spec_round`` spans
that began inside the window. A round verifies if any of its rows had a
draft; the others run the plain step alone. None where no such span
carries the count (a program that verifies every round, or a family
that does not speculate)."""


def read(ctx):
    spans = [s["args"] for s in ctx.spans if s["name"] == "spec_round"
             and "rounds_verified" in s["args"]]
    rounds = sum(a["rounds"] for a in spans)
    if not rounds:
        return None
    return 100.0 * sum(a["rounds_verified"] for a in spans) / rounds
