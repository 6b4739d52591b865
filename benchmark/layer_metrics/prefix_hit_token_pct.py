"""Share of the window's prompt tokens that the KV manager served from rows
already in the cache, whichever tier found them (a live slot's pages shared
copy-on-write, the slot's own rows, a prefix-cache splice, a host restore):
``reused_rows`` over ``prompt_tokens`` of the ``admission`` spans that began
inside the window."""


def read(ctx):
    adm = [s["args"] for s in ctx.spans if s["name"] == "admission"
           and "prompt_tokens" in s["args"]]
    total = sum(a["prompt_tokens"] for a in adm)
    if not total:
        return None
    return 100.0 * sum(a.get("reused_rows", 0) for a in adm) / total
