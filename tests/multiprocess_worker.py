"""Worker for the real multi-process SPMD test (multiprocess_test.py).

Runs as ``python multiprocess_worker.py <rank> <port>``: joins a 2-process
jax.distributed cluster (4 virtual CPU devices per process -> 8 global),
builds the framework's data x model mesh spanning both processes, feeds its
local half of the batch through data/feed.py, and runs 5 train steps.  The
cross-process gradient all-reduce and head-sharded matmul collectives ride
the gloo backend — the CPU stand-in for the reference's multi-host story
(SURVEY.md §5.8: TF distributed session over DCN)."""
import os
import sys

rank = int(sys.argv[1])
port = sys.argv[2]
NPROCS = int(sys.argv[3]) if len(sys.argv) > 3 else 2
CKPT_DIR = sys.argv[4] if len(sys.argv) > 4 else ""
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8 // NPROCS)
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=NPROCS,
                           process_id=rank)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from homebrewnlp_tpu.config import Config  # noqa: E402
from homebrewnlp_tpu.data import synthetic_text_batch, to_global  # noqa: E402
from homebrewnlp_tpu.parallel import make_mesh  # noqa: E402
from homebrewnlp_tpu.train import Trainer  # noqa: E402

assert jax.process_count() == NPROCS, jax.process_count()
assert len(jax.devices()) == 8


def run_case(name, **over):
    base = dict(
        model_mode="gpt", use_video=False, sequence_length=16, heads=4,
        features_per_head=32, vocab_size=64, depth=1, train_batch_size=8,
        memory_reduction_strategy="none", optimizer="adam-learning_rate",
        learning_rate=1e-2, weight_decay=0.0,
        intermediate_feed_forward_multiplier_multiplier=0.5,
        block_config=[{"layer": ["norm-shift-scale", "feed_forward-in:relu"]}])
    base.update(over)
    cfg = Config(base)
    mesh = make_mesh(cfg)
    trainer = Trainer(cfg, mesh)
    full = synthetic_text_batch(cfg, 0)
    # processes sharing a data coordinate (pipe spanning hosts) load the
    # SAME rows — the data_slice_for_process contract
    from homebrewnlp_tpu.data.feed import data_slice_for_process
    si, sc = data_slice_for_process(mesh)
    rows = full["token_x"].shape[0] // sc
    local = {k: v[si * rows:(si + 1) * rows] for k, v in full.items()}
    state = trainer.init(to_global(local, cfg, mesh))
    losses = []
    for i in range(5):
        gb = to_global(local, cfg, mesh)
        state, m = trainer.step(state, gb, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (name, losses)
    # full-precision full sequence: the harness compares this line across
    # ranks to catch any cross-process divergence, not just the endpoints
    print(f"rank{rank}: {name} mesh={dict(mesh.shape)} "
          f"losses={[x.hex() for x in losses]}", flush=True)
    return cfg, mesh, trainer, state, local


if NPROCS == 2:
    # 1) data x model parallel: cross-process gradient all-reduce +
    #    head-sharded matmul collectives
    run_case("dp_tp")
    # 2) data x sequence x model: ring attention's ppermute ring crosses the
    #    process boundary (long-context sequence parallelism over "DCN")
    run_case("dp_sp_tp", heads=2, sequence_parallel=2, sequence_length=32,
             block_config=[
                 {"layer": ["norm-shift-scale",
                            "attention-in:relu-dot_product-embedded-relative"]},
                 {"layer": ["norm-shift-scale", "feed_forward-in:relu"]}])
else:
    # 4 processes x 2 devices (VERDICT r3 item 7):
    # a) pipe axis ACROSS process boundaries: pipeline_parallel=4 with
    #    data=2 makes each pipe ring span two processes — the GPipe
    #    activation hops and their gradient transposes ride the gloo "DCN"
    run_case("dp_pp", heads=1, pipeline_parallel=4, depth=4,
             memory_reduction_strategy="none")
    # ...and the 1F1B interleaved schedule over the same cross-process ring
    run_case("dp_pp_1f1b", heads=1, pipeline_parallel=4, depth=4,
             pipeline_schedule="1f1b", memory_reduction_strategy="none")
    # c) seq x pipe COMPOSED across processes: the nested seq-manual ring
    #    (ops/ring.py) rotates K/V blocks over one process boundary while
    #    the pipe ring hops activations over another — both collectives
    #    ride the gloo "DCN" inside one 1F1B step
    run_case("sp_pp_1f1b", heads=2, sequence_parallel=2, pipeline_parallel=2,
             depth=2, sequence_length=32, train_batch_size=16,
             pipeline_schedule="1f1b", memory_reduction_strategy="none",
             block_config=[
                 {"layer": ["norm-shift-scale",
                            "attention-in:relu-dot_product-embedded-relative"]},
                 {"layer": ["norm-shift-scale", "feed_forward-in:relu"]}])
    # b) orbax save/restore under jax.distributed with PER-PROCESS data
    #    cursors (each host's reader position differs; the sidecar is
    #    per-process like the reference's per-host DataLog)
    from homebrewnlp_tpu.train import Checkpointer
    cfg, mesh, trainer, state, local = run_case("dp_tp_ckpt")
    assert CKPT_DIR, "4-process mode needs a shared checkpoint dir argv[4]"
    ckpt = Checkpointer(CKPT_DIR)
    ckpt.save(state, data_state={"cursor": 1000 + rank})
    ckpt.wait()
    trainer2 = Trainer(cfg, make_mesh(cfg))
    template = trainer2.init(to_global(local, cfg, mesh))
    restored, ds = Checkpointer(CKPT_DIR).restore(template)
    assert int(restored.step) == 5, int(restored.step)
    assert ds == {"cursor": 1000 + rank}, ds
    import numpy as np
    for k in state.params:
        for sa, sb in zip(state.params[k].addressable_shards,
                          restored.params[k].addressable_shards):
            np.testing.assert_array_equal(np.asarray(sa.data),
                                          np.asarray(sb.data), err_msg=k)
    print(f"rank{rank}: ckpt restored step=5 cursor={ds['cursor']}",
          flush=True)
print(f"rank{rank}: MULTIPROC_OK", flush=True)
