"""MB of per-request state one decode step takes in and hands back for each
member: twice the SSD, conv and K/V bytes of one slot's caches (the
``state_bytes_member`` counter)."""


def read(ctx):
    member = ctx.counters.get("state_bytes_member")
    if not member:
        return None
    return 2.0 * member / 1e6
