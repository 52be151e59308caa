"""Requests per micro-batch in the window: the Service's ``completed``
over its ``batches`` counter, both taken as the window's increments."""


def read(ctx):
    a, b = ctx.after.get("service"), ctx.before.get("service")
    if a is None:
        return None
    batches = a["batches"] - b["batches"]
    return (a["completed"] - b["completed"]) / batches if batches else None
