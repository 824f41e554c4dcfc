"""The plain reference against the program, at 0.25@96 on the CPU.

The reference imports nothing of the program; these tests do, to show that
the two agree exactly where they should and that the int4 control does
not."""
import numpy as np
import pytest

from lib import reference as R
from lib import traffic
from small import SMALL, small_cell


@pytest.fixture(scope="module")
def cfg():
    return small_cell("reorder.backlog").config


@pytest.fixture(scope="module")
def program(cfg):
    import repro.deploy as deploy
    from repro.graphs import mobilenet_v1_graph
    return deploy.build(mobilenet_v1_graph(SMALL["alpha"],
                                           SMALL["resolution"]),
                        quantize=True)


def test_layers_follow_the_program_graph(cfg, program):
    ops = program.qmodel.graph.default_schedule()
    ls = R.layers(cfg)
    assert [op.kind for op in ops] == ["q" + layer.kind for layer in ls]
    for op, layer in zip(ops, ls):
        shape = program.qmodel.graph.tensors[op.output].shape
        assert tuple(shape) == (layer.h_out, layer.h_out, layer.cout)


def test_quantization_is_the_programs(cfg, program):
    qm = R.quantize_model(cfg)
    g = program.qmodel.graph
    names = ["input"] + [op.output for op in g.default_schedule()]
    for qp, name in zip(qm.act, names):
        p = program.qmodel.qparams[name]
        assert (p.scale, p.zero_point) == (qp.scale, qp.zp), name
    for op, w, m in zip(g.default_schedule(), qm.weights, qm.mults):
        if w is not None:
            np.testing.assert_array_equal(op.attrs["weight_q"], w)
            assert op.attrs["mult"] == m


def test_served_logits_equal_the_reference(cfg, program):
    imgs = traffic.pool_images(cfg, 6, 2**31 + 11)
    qm = R.quantize_model(cfg)
    q = qm.quantize_input(imgs)
    for i in range(len(imgs)):
        edge = program.quantize_inputs({"input": imgs[i]})["input"]
        np.testing.assert_array_equal(edge, q[i])
    outs = program.serve([{"input": x} for x in q], micro_batch=4)
    got = [next(iter(o.values())) for o in outs]
    want = R.logits(qm, q, block=4)
    assert R.compare(got, want) == {"missing_answers": 0,
                                    "wrong_answers": 0,
                                    "max_logit_gap_lsb": 0}


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_int4_control_fails_the_comparison(cfg, seed):
    """The control: the reference with int4 weights in the program's place
    must come out not correct on every seed."""
    imgs = traffic.pool_images(cfg, 8, seed)
    qm8 = R.quantize_model(cfg)
    qm4 = R.quantize_model(cfg, weight_bits=4)
    want = R.logits(qm8, qm8.quantize_input(imgs), block=8)
    ctrl = R.logits(qm4, qm4.quantize_input(imgs), block=8)
    nums = R.compare(list(ctrl), want)
    assert nums["wrong_answers"] > 0 and nums["max_logit_gap_lsb"] > 0


def test_missing_answers_count():
    want = np.zeros((3, 2), np.int8)
    got = [np.zeros(2, np.int8), None, np.array([0, 3], np.int8)]
    assert R.compare(got, want) == {"missing_answers": 1,
                                    "wrong_answers": 1,
                                    "max_logit_gap_lsb": 3}
