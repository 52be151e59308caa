"""Share of the kernel's lanes that carried a request in the window:
requests completed over the lanes the engines' bucket calls occupied,
a call of ``b`` rows occupying ``lanes * ceil(b / lanes)``."""
import math


def _lanes_used(engine_stats, lanes):
    return {name: sum(lanes * math.ceil(int(b) / lanes) * n
                      for b, n in e["bucket_calls"].items())
            for name, e in engine_stats["per_engine"].items()}


def read(ctx):
    a, b = ctx.after.get("service"), ctx.before.get("service")
    if a is None:
        return None
    lanes = int(ctx.cell.config["lanes"])
    used_a = _lanes_used(a["engine"], lanes)
    used_b = _lanes_used(b["engine"], lanes)
    used = sum(v - used_b.get(k, 0) for k, v in used_a.items())
    served = a["completed"] - b["completed"]
    return 100.0 * served / used if used else None
