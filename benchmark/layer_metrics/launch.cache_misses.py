"""``hvd_compiles_total{cache="miss"}`` at the reading: programs the
process asked jax's persistent cache for and had to compile. 0 on a
warm run; more on a warm run means the cache lost an entry
(``benchmark/launch_view.py``)."""

from benchmark import launch_view


def read(ctx):
    return launch_view.cache_misses(ctx)
