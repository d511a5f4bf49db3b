"""The share of chip 0's busy time in instructions that no program scope
reaches, own or inherited: how much of the step ``scope_view`` cannot
name."""

from benchmark import scope_view


def read(ctx):
    return scope_view.unscoped_pct(ctx)
