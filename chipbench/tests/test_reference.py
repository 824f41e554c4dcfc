"""The plain reference against the program, at 0.25@96 on the CPU.

The reference (``models/mobilenet_v1.py`` on ``lib/reference.py``) imports
nothing of the program but inside ``program_graph``; these tests do, to
show that the two agree exactly where they should and that the int4
control does not.  A configuration names its model, and the harness loads
``models/<model>.py``: a new model is new files only."""
import hashlib
import json
import shutil

import numpy as np
import pytest

from conftest import BENCH, ROOT
from lib import harness
from lib import reference as R
from lib import traffic
from small import SMALL, small_cell

# The small cell's reference readings, recorded before MobileNet-v1 moved
# out of lib/reference.py into models/mobilenet_v1.py: the int8 logits of
# a pool of 8 at seed 2**31 + 5, and the 30 activation parameters.
PINNED_SEED = 2**31 + 5
PINNED_LOGITS = [[62, -11], [82, -9], [89, -12], [68, 19], [96, -7],
                 [127, 7], [66, -18], [49, 25]]
PINNED_ACT_SHA256 = ("76192496c875129baceca4bba059e50f"
                     "4b19bfd82c5ab1b13563e9b3126ed09d")
REORDER = "mobilenet_v1_1.0_192_int8.reorder"


@pytest.fixture(scope="module")
def cell():
    return small_cell("reorder.backlog")


@pytest.fixture(scope="module")
def cfg(cell):
    return cell.config


@pytest.fixture(scope="module")
def v1(cell):
    return cell.model


@pytest.fixture(scope="module")
def program(cfg, v1):
    import repro.deploy as deploy
    return deploy.build(v1.program_graph(cfg), quantize=True)


def _pool_logits(model, cfg):
    imgs = traffic.pool_images(cfg, 8, PINNED_SEED)
    qm = model.quantize_model(cfg)
    return qm, R.logits(model.int8_forward(qm), qm.quantize_input(imgs))


def _checkout(root, model: str):
    """``BENCHMARK.json`` and the reorder configuration under ``root``,
    the configuration naming ``model``."""
    shutil.copy(ROOT / "BENCHMARK.json", root)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    file = {c["name"]: c for c in bench["configs"]}[REORDER]["file"]
    config = json.loads((ROOT / file).read_text())
    config["model"] = model
    (root / file).parent.mkdir(parents=True)
    (root / file).write_text(json.dumps(config))


def test_the_configuration_names_its_model(cell):
    assert cell.config["model"] == "mobilenet_v1"
    assert cell.model.__file__ == str(BENCH / "models" / "mobilenet_v1.py")


def test_reference_reproduces_its_pinned_readings(cfg, v1):
    qm, got = _pool_logits(v1, cfg)
    assert got.dtype == np.int8 and got.tolist() == PINNED_LOGITS
    act = json.dumps([(a.scale, a.zp) for a in qm.act])
    assert hashlib.sha256(act.encode()).hexdigest() == PINNED_ACT_SHA256


def test_a_new_model_is_new_files_only(tmp_path):
    """A copy of the model file under another name, named by a
    configuration, is loaded by ``load_cell`` and gives the same logits."""
    models = tmp_path / "models"
    models.mkdir()
    shutil.copy(BENCH / "models" / "mobilenet_v1.py", models / "copy_v1.py")
    _checkout(tmp_path, "copy_v1")
    cell = harness.load_cell(tmp_path, "reorder.backlog", models=models)
    assert cell.model.__file__ == str(models / "copy_v1.py")
    cell.config.update(SMALL)
    _, got = _pool_logits(cell.model, cell.config)
    assert got.tolist() == PINNED_LOGITS


def test_unknown_model_fails_at_load_cell(tmp_path):
    _checkout(tmp_path, "no_such_model")
    with pytest.raises(FileNotFoundError, match="'no_such_model'") as e:
        harness.load_cell(tmp_path, "reorder.backlog")
    assert str(harness.MODELS) in str(e.value)


def test_layers_follow_the_program_graph(cfg, v1, program):
    ops = program.qmodel.graph.default_schedule()
    ls = v1.layers(cfg)
    assert [op.kind for op in ops] == ["q" + layer.kind for layer in ls]
    for op, layer in zip(ops, ls):
        shape = program.qmodel.graph.tensors[op.output].shape
        assert tuple(shape) == (layer.h_out, layer.h_out, layer.cout)


def test_quantization_is_the_programs(cfg, v1, program):
    qm = v1.quantize_model(cfg)
    g = program.qmodel.graph
    names = ["input"] + [op.output for op in g.default_schedule()]
    for qp, name in zip(qm.act, names):
        p = program.qmodel.qparams[name]
        assert (p.scale, p.zero_point) == (qp.scale, qp.zp), name
    for op, w, m in zip(g.default_schedule(), qm.weights, qm.mults):
        if w is not None:
            np.testing.assert_array_equal(op.attrs["weight_q"], w)
            assert op.attrs["mult"] == m


def test_served_logits_equal_the_reference(cfg, v1, program):
    imgs = traffic.pool_images(cfg, 6, 2**31 + 11)
    qm = v1.quantize_model(cfg)
    q = qm.quantize_input(imgs)
    for i in range(len(imgs)):
        edge = program.quantize_inputs({"input": imgs[i]})["input"]
        np.testing.assert_array_equal(edge, q[i])
    outs = program.serve([{"input": x} for x in q], micro_batch=4)
    got = [next(iter(o.values())) for o in outs]
    want = R.logits(v1.int8_forward(qm), q, block=4)
    assert R.compare(got, want) == {"missing_answers": 0,
                                    "wrong_answers": 0,
                                    "max_logit_gap_lsb": 0}


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_int4_control_fails_the_comparison(cfg, v1, seed):
    """The control: the reference with int4 weights in the program's place
    must come out not correct on every seed."""
    imgs = traffic.pool_images(cfg, 8, seed)
    qm8 = v1.quantize_model(cfg)
    qm4 = v1.quantize_model(cfg, weight_bits=4)
    want = R.logits(v1.int8_forward(qm8), qm8.quantize_input(imgs), block=8)
    ctrl = R.logits(v1.int8_forward(qm4), qm4.quantize_input(imgs), block=8)
    nums = R.compare(list(ctrl), want)
    assert nums["wrong_answers"] > 0 and nums["max_logit_gap_lsb"] > 0


def test_missing_answers_count():
    want = np.zeros((3, 2), np.int8)
    got = [np.zeros(2, np.int8), None, np.array([0, 3], np.int8)]
    assert R.compare(got, want) == {"missing_answers": 1,
                                    "wrong_answers": 1,
                                    "max_logit_gap_lsb": 3}
