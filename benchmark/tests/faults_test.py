"""A whole run with the timed path broken underneath reads `correct` false:
once for a step that returns its state unchanged, once for half of the batch
left out with the mean taken over the rest.  (One chip: no exchange to leave
out.  Training: no token to alter.)"""
import pytest

from conftest import CELLS


def frozen_state(real):
    """`Trainer.step` computes its metrics and hands the old state back."""
    import jax
    import jax.numpy as jnp

    def step(self, state, batch, rng):
        _, metrics = real(self, jax.tree_util.tree_map(jnp.copy, state),
                          batch, rng)
        return state, metrics

    return step


def half_batch(real):
    """`Trainer.step` sees the first half of every batch only."""
    from homebrewnlp_tpu.nd import NT

    def step(self, state, batch, rng):
        half = {k: NT(t.x[:t.x.shape[0] // 2], t.names)
                for k, t in batch.items()}
        return real(self, state, half, rng)

    return step


@pytest.mark.parametrize("fault", [frozen_state, half_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(run_cell, cell, fault):
    rc, line, err = run_cell(cell, seed=21, fault=fault)
    assert rc == 0, err
    assert line["correct"] is False
    assert any(row["value"] > row["limit"] for row in line["checks"].values())
