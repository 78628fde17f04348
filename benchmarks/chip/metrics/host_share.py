"""Per cent of the window the worker thread spent outside sub-blocks:
merging statistics, handing blocks to the forwarder, E_T feedback."""


def read(ctx):
    w = ctx.get('window')
    if not w or w['seconds'] <= 0:
        return None
    return 100.0 * (1.0 - w['inside_s'] / w['seconds'])
