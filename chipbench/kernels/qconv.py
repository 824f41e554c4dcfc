"""The kernels that serve a ``qconv`` operator: a 1x1, stride-1 ``qconv``
with no explicit padding runs ``qconv1x1``, any other ``qconv`` runs
``qconv``, as the lowering chooses."""
import math


def _unpadded(pads) -> bool:
    return pads is None or tuple(pads) == (0, 0)


def kernel_of(attrs: dict) -> str:
    if (attrs["k"] == 1 and attrs["stride"] == 1
            and _unpadded(attrs.get("pex_pads"))
            and _unpadded(attrs.get("pex_wpads"))):
        return "qconv1x1"
    return "qconv"


def call_cost(attrs: dict, in_shapes, out_shape, lanes: int):
    """(operations, least bytes) of one call over ``lanes``: 2 x
    multiply-accumulates x lanes; each lane's input and output (int8, 1 B
    each) plus the int8 weights once."""
    (in_shape,) = in_shapes
    oh, ow, cout = out_shape
    macs = oh * ow * cout * attrs["k"] ** 2 * in_shape[-1]
    act = math.prod(in_shape) + math.prod(out_shape)
    return 2 * macs * lanes, act * lanes + int(attrs["weight_q"].nbytes)
