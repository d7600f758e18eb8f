"""A host-clock span the harness itself took around a call into a layer."""


def read(ctx, key, scale=1.0):
    value = ctx["spans"].get(key)
    return None if value is None else value * scale
