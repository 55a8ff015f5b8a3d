"""The expert exchange (DESIGN.md §10): the all-to-all pair of a routed
MoE layer, its virtual emulation, its telemetry and its bytes model
(`exchange.py`)."""
from repro.comm.exchange import (Exchange, combine, comm_zero,  # noqa: F401
                                 dispatch, exchange_cost, layer_cost,
                                 routed_capacity, step_cost, vcombine,
                                 vdispatch)
