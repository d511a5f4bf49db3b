"""The Mamba layers' selective scans as a share of their roofline: the
least time for the float32 rows the scan must move (x', Delta and y
forward; those, y's gradient and two gradients backward) and its
multiplies and adds (``flops_phi4flash.scan_work``; the memory roof
binds, the work is the vector unit's and ``peaks.json`` has no vector
peak) over ``ssm.scan_ms`` (``benchmark/ssm_view.py``)."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.scan_roofline(ctx)
