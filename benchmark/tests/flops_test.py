"""`flops.py` (from shapes) against a jaxpr count of the program's unfused,
no-remat twin: forward + backward of the same toy model."""
import json

import pytest

from conftest import TOY_MANIFEST


@pytest.mark.parametrize("name", ["32big_mixer", "32mixer_group"])
def test_required_flops_match_a_jaxpr_count(name):
    import jax
    import flops
    import run
    from homebrewnlp_tpu.config import Config
    from homebrewnlp_tpu.models import build
    from homebrewnlp_tpu.models.ctx import Ctx
    from homebrewnlp_tpu.train.flops import jaxpr_flops
    from homebrewnlp_tpu.utils import random_text_batch
    with open(TOY_MANIFEST) as f:
        cell = run.find_cell(json.load(f), "toy-" + name + ".train")
    raw = {k: v for k, v in cell["config"].items() if k != "benchmark"}
    raw.update(reversible_remat_blocks=False, fused_mixer_block=False,
               memory_reduction_strategy="none", calculation_dtype="float32")
    cfg = Config(raw)
    batch = random_text_batch(cfg)
    sz = cell["reference"].Sizes.from_config(raw)
    params = cell["reference"].init_weights(sz, 0)

    def loss(p):
        return build(Ctx(cfg, params=p, train=True), batch).loss

    counted = jaxpr_flops(jax.make_jaxpr(jax.grad(loss))(params))
    ours = flops.train_step_flops(raw, raw["train_batch_size"], dense_maps=True)
    assert abs(counted - ours) / ours < 0.05, (counted, ours)
    assert flops.train_step_flops(raw, raw["train_batch_size"]) < ours
