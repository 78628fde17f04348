"""Milliseconds per generation inside ``BlockSampler.run_subblock``.

The worker thread's wall time inside the sub-block calls that ended in
the window (each ends in a host copy, so it waits for the device),
divided by the generations they ran.
"""


def read(ctx):
    w = ctx.get('window')
    if not w or not w['generations']:
        return None
    return 1e3 * w['inside_s'] / w['generations']
