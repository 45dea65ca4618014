"""Seconds of ``ShardedIndex.build`` (bitmap index of every shard) on the
benchmark's clock."""


def read(ctx):
    return ctx.build.get("index_s")
