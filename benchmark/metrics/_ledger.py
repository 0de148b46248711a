"""What the readers of the server's two ledgers share (ISSUE 38; the
leading underscore keeps this file out of ``readers()``): ``stats()``
gives ``lock_held_ms``, ``lock_wait_ms`` and ``request_ms`` as name ->
``[count, total ms]``, and a reading is the difference between the two
snapshots at the window's edges. A program without the ledger, or
without the name, gives ``None``."""


def gained(ctx: dict, key: str, name: str):
    """``(count, total ms)`` that ``key[name]`` gained between the
    snapshots."""
    a = (ctx["stats_start"].get(key) or {}).get(name)
    b = (ctx["stats_end"].get(key) or {}).get(name)
    if a is None or b is None:
        return None
    return b[0] - a[0], b[1] - a[1]


def gained_ms(ctx: dict, key: str, names) -> float | None:
    """Milliseconds the named entries of ``key`` gained together."""
    parts = [gained(ctx, key, name) for name in names]
    return None if None in parts else sum(ms for _, ms in parts)


def names(ctx: dict, key: str):
    """The names both snapshots have under ``key``."""
    a, b = ctx["stats_start"].get(key), ctx["stats_end"].get(key)
    return None if a is None or b is None else sorted(set(a) & set(b))
