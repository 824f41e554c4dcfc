"""The kernel that serves a ``qadd`` operator (an int8 residual add):
``qadd``."""
import math


def kernel_of(attrs: dict) -> str:
    return "qadd"


def call_cost(attrs: dict, in_shapes, out_shape, lanes: int):
    """(operations, least bytes) of one call over ``lanes``: 4 integer
    operations per output element (two multiply-accumulates of the
    rescaled inputs, the rounding shift and the zero point) x lanes; each
    lane's two inputs and its output (int8, 1 B each).  No weights, so
    the add is memory-bound."""
    n = math.prod(out_shape)
    act = sum(math.prod(s) for s in in_shapes) + n
    return 4 * n * lanes, act * lanes
