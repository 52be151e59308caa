"""Mean time a served request waited in the Service before its sweep
started: its ``queue`` plus ``coalesce`` spans (``coalesce`` holds the
``dispatch`` tail), from the Service's request span trees in the window."""


def read(ctx):
    per = {}
    for s in ctx.spans:
        if s.name in ("queue", "coalesce"):
            per.setdefault(s.trace_id, {})[s.name] = s.dur_s
    waits = [v["queue"] + v["coalesce"] for v in per.values()
             if len(v) == 2]
    return 1e3 * sum(waits) / len(waits) if waits else None
