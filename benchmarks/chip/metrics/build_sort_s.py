"""Seconds of the program's lexicographic sort (``core.sorting.lex_sort``)
on the benchmark's clock; nothing where the configuration stores rows
unsorted."""


def read(ctx):
    return ctx.build.get("sort_s")
