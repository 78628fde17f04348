"""Per cent of the traced stretch in which no operation ran on the
device (1 - busy union / traced span)."""


def read(ctx):
    t = ctx.get('trace')
    if not t:
        return None
    return 100.0 * t['idle_share']
