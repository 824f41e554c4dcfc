"""A run whose timed path is broken underneath must come out not correct.

The harness's look for a chip is skipped (the run is driven on the CPU at
0.25@96); the arena program the engine dispatches is wrapped so that it
makes one of the faults a served cell can have.  The exchange between
chips does not exist in these one-chip cells, so it has no case here."""
import jax.numpy as jnp
import pytest

from small import run_small


def _unchanged(ex, batch, out):
    """A step that returns its state unchanged."""
    return jnp.asarray(batch)


def _half(ex, batch, out):
    """Half of the lanes left out: their arenas come back uncomputed."""
    lanes = out.shape[1]
    return out.at[:, lanes // 2:].set(jnp.asarray(batch)[:, lanes // 2:])


def _altered(ex, batch, out):
    """One answer altered where it is produced: a logit of lane 0."""
    off, _ = ex.offsets[ex.graph.outputs[0]]
    return out.at[0, 0, off].add(jnp.uint8(1))


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
@pytest.mark.parametrize("workload", ["reorder.backlog", "tile224k.poisson"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, workload):
    from repro.mcu.compile import CompiledExecutor
    orig = CompiledExecutor.replicated_fn

    def broken(self, replicas):
        f = orig(self, replicas)
        return lambda batch: fault(self, batch, f(batch))

    monkeypatch.setattr(CompiledExecutor, "replicated_fn", broken)
    # offered load above what the CPU serves, so that dispatches fill
    out = run_small(workload, seconds=0.5, knee_rps=1000.0)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_sound_path_is_correct():
    assert run_small("tile224k.poisson", seconds=0.5)["correct"]
