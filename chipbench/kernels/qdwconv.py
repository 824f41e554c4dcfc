"""The kernel that serves a ``qdwconv`` operator: ``qdwconv``."""
import math


def kernel_of(attrs: dict) -> str:
    return "qdwconv"


def call_cost(attrs: dict, in_shapes, out_shape, lanes: int):
    """(operations, least bytes) of one call over ``lanes``: 2 x
    multiply-accumulates (one per output element and tap) x lanes; each
    lane's input and output (int8, 1 B each) plus the int8 weights once."""
    (in_shape,) = in_shapes
    oh, ow, cout = out_shape
    macs = oh * ow * cout * attrs["k"] ** 2
    act = math.prod(in_shape) + math.prod(out_shape)
    return 2 * macs * lanes, act * lanes + int(attrs["weight_q"].nbytes)
