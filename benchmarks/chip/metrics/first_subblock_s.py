"""Seconds of the warm-up sub-block: compile or cache load of the block
program and its first run, ended by ``block_until_ready``."""


def read(ctx):
    return ctx.get('first_subblock_s')
