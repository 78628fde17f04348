"""Seconds in ``build_run``: system build, propagator, sampler, manager."""


def read(ctx):
    return ctx.get('build_s')
