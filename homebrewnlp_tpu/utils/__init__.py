"""Shared utilities: config loading, the one-chip workload shapes, the
persistent compile cache and random batch construction (used by bench.py,
chip_smoke.py, __graft_entry__.py, tools/ and the CLI)."""
from __future__ import annotations

import json
import os
import typing

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> str:
    """Switch on XLA's persistent compilation cache so a restart (and the
    next process of the same chip call) deserialises the step executables
    instead of compiling them again.  THE one site that places the cache:

    - ``JAX_COMPILATION_CACHE_DIR`` in the environment: JAX has already read
      it into ``jax_compilation_cache_dir``; no directory is set in code, so
      whoever launched the process decides where the cache lives.
    - otherwise ``<checkout>/.jax_cache`` (git-ignored): a fixed path — a
      directory that moves with the user, a pid or the clock is never
      found again by the next process.

    ``JAX_ENABLE_COMPILATION_CACHE=false`` in the environment switches the
    cache off (JAX's own flag).  Returns the directory in use.

    Whoever turns the cache on also counts its hits: the compile log's
    listeners (``obs/compile_log.py``) are registered here, before the
    first build of every entry point."""
    import jax
    from ..obs import compile_log
    compile_log.install()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))
    # cache everything that took a noticeable compile (JAX's default floor
    # of 1 s skips the mid-sized init/sampler programs a warm start also
    # pays for); 0.1 s keeps the thousands of trivial programs out
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


#: How every cell and the chip smoke localise a reference config to ONE
#: chip — one definition, so bench.py, tools/ab_probe.py, tools/
#: byte_budget.py and chip_smoke.py build the same executables and share
#: persistent-cache entries.  ``slice_dtype`` (the device-resident param
#: copy) is bfloat16 because every number these cells ever produced was
#: taken with it and it is the cheaper residency; the configs' float32
#: compiles and trains on the chip too (flagship, 16 updates, loss 6.19 ->
#: 3.45, peak HBM 3.14 GB against 2.43 GB: chip runs of PR 21), so nothing
#: forces bf16 — following the configs is the benchmark issue's decision.
ONE_CHIP_COMMON = dict(use_checkpointing=False, calc_accuracy=False,
                       tpu_size=1, slice_dtype="bfloat16")
#: The three reference training shapes (BASELINE.md), batch cut for one
#: chip: flagship 1024 -> 8, throughput shape 4096 -> 64, long-context
#: shape 256 -> 8.
ONE_CHIP_WORKLOADS = {
    "32big_mixer": dict(train_batch_size=8),
    "32mixer_group": dict(train_batch_size=64),
    "32ctx_mixer": dict(train_batch_size=8),
}


def one_chip_config(name: str, **overrides):
    """``configs/<name>.json`` localised to one chip as the benchmark cell
    of that name runs it (``ONE_CHIP_COMMON`` + the cell's batch), with
    keyword overrides applied last."""
    return load_config(f"configs/{name}.json",
                       **{**ONE_CHIP_COMMON, **ONE_CHIP_WORKLOADS[name],
                          **overrides})


def load_config(path: str, **overrides):
    """Config from JSON with keyword overrides applied before derivation."""
    from ..config import Config
    if not os.path.isabs(path) and not os.path.exists(path):
        path = os.path.join(REPO_ROOT, path)
    with open(path) as f:
        raw = json.load(f)
    raw.update(overrides)
    return Config(raw)


def random_text_batch(cfg, seed: int = 0) -> typing.Dict[str, typing.Any]:
    """Uniform-random token batch as NTs (model input shape, reference
    dataclass.py:310-337 text entries)."""
    import jax
    from ..data.feed import TEXT_AXES as names
    from ..nd import NT
    shape = (cfg.train_batch_size * cfg.macro_batching,
             cfg.sequence_length // cfg.token_patch_size,
             cfg.token_patch_size)
    kx, ky = jax.random.split(jax.random.key(seed))
    return {
        "token_x": NT(jax.random.randint(kx, shape, 0, cfg.vocab_size), names),
        "token_y": NT(jax.random.randint(ky, shape, 0, cfg.vocab_size), names),
    }
