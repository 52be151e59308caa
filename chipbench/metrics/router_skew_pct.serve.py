"""How far the busiest replica ran above the mean in the window: the
router's per-slot ``samples`` increments, (max / mean - 1) in percent;
0 is an even spread."""


def read(ctx):
    a, b = ctx.after.get("service"), ctx.before.get("service")
    if a is None or "router" not in a:
        return None
    got = [sa["samples"] - sb["samples"] for sa, sb in
           zip(a["router"]["slots"], b["router"]["slots"])]
    mean = sum(got) / len(got)
    return 100.0 * (max(got) / mean - 1.0) if mean else None
