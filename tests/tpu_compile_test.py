"""Compiles for a v5e that is described, not attached (libtpu compiles with no
chip: PERF.md §7, "the off-chip compile").  They size HLO and ask Mosaic to
accept a kernel; they never give a time.  The topology is described inside a
fixture, so every xdist worker collects the same tests and only the worker
that runs this file loads libtpu; keep such tests in this one file."""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from homebrewnlp_tpu.config import Config
from homebrewnlp_tpu.models import build
from homebrewnlp_tpu.models.ctx import Ctx
from homebrewnlp_tpu.nd import NT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUP_CELL_CONFIG = os.path.join(REPO, "benchmark", "configs",
                                 "32mixer_group.json")
TOKEN_NAMES = ("batch", "sequence", "language_token_patch")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out so the next run does not warn."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def compiled_gradient_hlo(cfg: Config, sharding) -> str:
    """Optimized HLO of `jax.grad` of the model's loss, parameters and
    tokens given as shapes on the described chip."""
    shape = (cfg.train_batch_size, cfg.sequence_length, cfg.token_patch_size)
    tok = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    def batch(tx, ty):
        return {"token_x": NT(tx, TOKEN_NAMES), "token_y": NT(ty, TOKEN_NAMES)}

    def collect(tx, ty):
        ctx = Ctx(cfg, params=None, seed=0, train=False)
        build(ctx, batch(tx, ty))
        return ctx.collected

    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
              for k, v in jax.eval_shape(collect, tok, tok).items()}

    def loss(p, tx, ty):
        ctx = Ctx(cfg, params=p, train=True, rng=jax.random.key(0))
        return build(ctx, batch(tx, ty)).loss

    return jax.jit(jax.grad(loss)).lower(params, tok, tok).compile().as_text()


def entry_instructions(hlo: str) -> dict:
    """name -> (opcode, operand names, the instruction's text) of the entry
    computation."""
    return instructions(
        re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo, re.S | re.M).group(1))


def instructions(computation: str) -> dict:
    """The same for the body of any one computation."""
    out = {}
    for line in computation.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        head = m.group(2).split(", metadata=")[0]
        call = re.search(r"[\]})] ([\w\-]+)\((.*)", head)
        out[m.group(1)] = (call.group(1), re.findall(r"%([\w.\-]+)",
                                                     call.group(2)), line)
    return out


def mosaic_calls(insts: dict) -> dict:
    """name -> operand names of the entry computation's Mosaic kernels."""
    return {name: operands for name, (opcode, operands, line) in insts.items()
            if opcode == "custom-call" and "tpu_custom_call" in line}


def copies_beside(insts: dict, name: str, least: int) -> list:
    """Copies and transposes of `least` elements or more that feed the
    instruction `name` or take its results (through bitcasts and tuple
    elements)."""
    def resolve(n):
        while insts[n][0] in ("bitcast", "get-tuple-element"):
            n = insts[n][1][0]
        return n

    def big(n):
        dims = re.match(r"\(?\w+\[([\d,]*)\]", insts[n][2].split(
            " = ", 1)[1]).group(1)
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        return size >= least

    users = {}
    for user, (_, operands, _) in insts.items():
        for o in operands:
            if o in insts:      # not a called computation
                users.setdefault(o, []).append(user)
    around = [resolve(o) for o in insts[name][1] if o in insts]
    pending = list(users.get(name, []))
    while pending:
        user = pending.pop()
        if insts[user][0] in ("bitcast", "get-tuple-element"):
            pending += users.get(user, [])
        else:
            around.append(user)
    return [insts[n][2].strip()[:120] for n in around
            if (insts[n][0] in ("copy", "transpose", "copy-start")
                or n.startswith("copy_"))   # a fusion that only copies
            and big(n)]


def test_fused_mixer_block_crosses_its_kernels_without_a_copy(
        one_chip, no_compile_cache, monkeypatch):
    """The only test that sees a layout.  XLA stores the group model's
    stream as [B,S,H,K]{1,3,2,0}, physically [B,H,K,S]; ops/pallas_mixer
    hands its kernels that order so both transposes at the call boundary are
    bitcasts.  The day a change elsewhere makes XLA hold the stream another
    way, activation-sized copies come back under the fused block (192 an
    update and 15% of the 32mixer_group.train step before PR 26) and this
    fails; so does a rename of the two calls, which
    benchmark/layer_metrics/mixer_block_roofline.json finds by name."""
    import homebrewnlp_tpu.ops as ops
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    with open(GROUP_CELL_CONFIG) as f:
        raw = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    depth = 2
    cfg = Config({**raw, "depth": depth})
    hlo = compiled_gradient_hlo(cfg, one_chip)
    insts = entry_instructions(hlo)

    n_b, seq = cfg.train_batch_size, cfg.sequence_length
    n_h, key = cfg.heads, cfg.features_per_head
    activation = re.compile(r"bf16\[(%s)\]" % "|".join(
        ",".join(map(str, dims)) for dims in (
            (n_b, seq, n_h, key), (n_b, n_h, key, seq), (n_h, n_b, seq, key),
            (n_b, seq, n_h * key))))
    fused_block = re.compile(r'op_name="[^"]*/d\d+_1/block_/')

    moved = [line.strip()[:160] for opcode, _, line in insts.values()
             if opcode in ("copy", "transpose", "copy-start")
             and activation.match(line.split(" = ", 1)[1])
             and fused_block.search(line)]
    assert not moved, moved

    def producer(name):
        while insts[name][0] in ("bitcast", "get-tuple-element"):
            name = insts[name][1][0]
        return insts[name][0]

    kernels = {name: operands for name, (opcode, operands, line)
               in insts.items()
               if opcode == "custom-call" and "tpu_custom_call" in line
               and fused_block.search(line)}
    # forward + replay + backward a fused block; XLA merges the last
    # block's replay with the forward it repeats when nothing lies between
    assert 3 * depth - 1 <= len(kernels) <= 3 * depth, sorted(kernels)
    for name, operands in kernels.items():
        assert re.match(r"_(fwd|bwd)_pallas(\.\d+)?$", name), name
        fed_by = {producer(o) for o in operands
                  if activation.match(insts[o][2].split(" = ", 1)[1])}
        assert fed_by and fed_by <= {"fusion", "custom-call"}, (name, fed_by)

    # ops/pallas_mixer's optimization_barrier at work: each reversible block's
    # input is rebuilt by one subtract (XLA drops the first block's).  Without
    # the barrier XLA sinks the kernel's transpose through the stream's
    # residual add, nothing needs the stream itself in memory any more, and
    # every consumer re-derives it from all earlier blocks' outputs: the
    # subtracts multiply (18 for 7 at depth 4) and depth 32 needs 29 GB.
    rebuilt = len(re.findall(r'op_name="[^"]*/body/sub"', hlo))
    assert rebuilt <= 2 * depth, rebuilt


def test_delta_rule_gradient_runs_two_kernels_and_no_chunk_scan(
        one_chip, no_compile_cache, monkeypatch):
    """`jax.grad` of `chunked_kda` at the shape of kimi_linear_48b.train:
    Mosaic accepts ops/pallas_kda.py's forward and backward, the scan over
    groups of chunks (its `while` carried bf16[32,8,2,32,32,128]) is gone and
    only the state's walk loops, and nothing the size of an operand is copied
    on its own next to a kernel: the heads-major transposes fuse into what
    makes the operands, the results enter the walk as they are written."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    n_b, seq, n_h, key = 2, 8192, 32, 128

    def shape(kind, *dims):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)

    stream = shape(jnp.bfloat16, n_b, seq, n_h, key)

    def loss(q, k, v, g, beta):
        # operands made by a fusion, as the layer's projections make them
        out = chunked_kda(q * 2, k * 2, v * 2, g * 2, beta * 2)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    hlo = jax.jit(jax.grad(loss, range(5))).lower(
        stream, stream, stream, shape(jnp.float32, n_b, seq, n_h, key),
        shape(jnp.float32, n_b, seq, n_h)).compile().as_text()
    insts = entry_instructions(hlo)
    kernels = mosaic_calls(insts)
    assert sorted(re.search(r"jit\((_kda_chunks_\w+)\)", insts[name][2]).group(1)
                  for name in kernels) == ["_kda_chunks_bwd",
                                           "_kda_chunks_fwd"], sorted(kernels)
    carried = [line for opcode, _, line in insts.values()
               if opcode == "while"]
    assert carried and not any("bf16[32,8,2,32,32,128]" in line
                               for line in carried), carried
    for name in kernels:
        moved = copies_beside(insts, name, n_b * seq * n_h * key)
        assert not moved, (name, moved)


def test_delta_rule_gradient_at_16_heads_and_beta_to_2_runs_two_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """The same at the shape of solar_open2_250b.train (one sequence, a
    host's share of 16 heads) with `wide_beta`: Mosaic accepts the inverse
    squared inside blocks of 8 tokens and merged pair by pair (masks by
    `iota // size` on a `[128, 128]` span, two more products a merge), in
    the forward and in the backward."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    n_b, seq, n_h, key = 1, 8192, 16, 128

    def shape(kind, *dims):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)

    stream = shape(jnp.bfloat16, n_b, seq, n_h, key)

    def loss(q, k, v, g, beta):
        out = chunked_kda(q * 2, k * 2, v * 2, g * 2, beta * 2,
                          wide_beta=True)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    hlo = jax.jit(jax.grad(loss, range(5))).lower(
        stream, stream, stream, shape(jnp.float32, n_b, seq, n_h, key),
        shape(jnp.float32, n_b, seq, n_h)).compile().as_text()
    insts = entry_instructions(hlo)
    kernels = mosaic_calls(insts)
    assert sorted(re.search(r"jit\((_kda_chunks_\w+)\)", insts[name][2]).group(1)
                  for name in kernels) == ["_kda_chunks_bwd",
                                           "_kda_chunks_fwd"], sorted(kernels)
    for name in kernels:
        # at a quarter of the Kimi operands the compiler prefetches some
        # into fast memory: a `copy-start` of the same shape and tiling
        moved = [line for line in copies_beside(insts, name,
                                                n_b * seq * n_h * key)
                 if not _prefetch(line)]
        assert not moved, (name, moved)


def test_mla_gradient_runs_two_kernels_and_no_score_tile(
        one_chip, no_compile_cache, monkeypatch):
    """`jax.grad` of the `mla` layer at the widths of kimi_linear_48b.train
    (32 heads, keys 192 wide, values 128; the sequence whole, since at a
    quarter of it XLA moves a 33 MB operand into fast memory ahead of the
    call and that move reads as a copy): Mosaic accepts ops/pallas_mla.py's
    forward and backward with a contraction of one and a half lane tiles,
    they are the only
    custom calls, nothing shaped like a score tile ([., ., R, R] for the
    kernels' block or the unrolled tiles' 1,024 rows) is left under `mla_`,
    and nothing the size of an operand is copied on its own next to a
    kernel (the heads-major transposes fuse into the operands' producers)."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.models.ctx import Args
    from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi_linear_48b.json")) as f:
        raw = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = Config(raw)
    seq = cfg.sequence_length
    names = ("batch", "sequence", "heads", "features_per_head")
    n_b, n_h = cfg.train_batch_size, cfg.heads

    def layer(params, x):
        ctx = Ctx(cfg, params=params, train=params is not None)
        out = ctx.scoped("mla_", LAYER_FUNCTIONS["mla"],
                         Args(ctx, NT(x, names), []))
        return out.x, ctx.collected

    x = jax.ShapeDtypeStruct((n_b, seq, n_h, cfg.features_per_head),
                             jnp.bfloat16, sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in jax.eval_shape(lambda x: layer(None, x)[1],
                                         x).items()}

    def loss(p, x):
        # the stream made by a fusion, as the block's norm makes it
        return jnp.sum(jnp.square(layer(p, x * 2)[0].astype(jnp.float32)))

    hlo = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    insts = entry_instructions(hlo)
    kernels = mosaic_calls(insts)
    assert sorted(re.search(r"jit\((_mla_attention_\w+)\)",
                            insts[name][2]).group(1)
                  for name in kernels) == ["_mla_attention_bwd",
                                           "_mla_attention_fwd"], sorted(
                                               kernels)
    tiles = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(r"= \(?f32\[\d+,\d+,(%d,%d|1024,1024)\]"
                          % (BLOCK, BLOCK), line) and "mla_" in line]
    assert not tiles, tiles
    for name in kernels:
        moved = copies_beside(insts, name, n_b * seq * n_h * cfg.v_head_dim)
        assert not moved, (name, moved)


@pytest.mark.parametrize("layer_type", ["sliding_attention", "full_attention"])
def test_gqa_gradient_runs_two_kernels_and_repeats_no_kv_head(
        layer_type, one_chip, no_compile_cache, monkeypatch):
    """`jax.grad` of the `gqa` layer at the widths of mellum2_12b.train (32
    query heads over 4 K/V heads, 128 wide, 8,192 tokens; a window of 1,024
    on the sliding layer): Mosaic accepts ops/pallas_mla.py's forward and
    backward with a K/V head shared by a group of 8 and with the band-limited
    walk, they are the only custom calls, `k` and `v` reach them with their
    4 heads (nothing of 32 heads' size is made from them), nothing shaped
    like a score tile is left under `gqa_`, and nothing the size of `q` is
    copied on its own next to a kernel."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.models.ctx import Args
    from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2_12b.json")) as f:
        raw = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = Config(raw)
    seq = cfg.sequence_length
    names = ("batch", "sequence", "heads", "features_per_head")
    n_b, n_h = cfg.train_batch_size, cfg.heads

    def layer(params, x):
        ctx = Ctx(cfg, params=params, train=params is not None)
        out = ctx.scoped("gqa_", LAYER_FUNCTIONS["gqa"],
                         Args(ctx, NT(x, names), [layer_type]))
        return out.x, ctx.collected

    x = jax.ShapeDtypeStruct((n_b, seq, n_h, cfg.features_per_head),
                             jnp.bfloat16, sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in jax.eval_shape(lambda x: layer(None, x)[1],
                                         x).items()}

    def loss(p, x):
        # the stream made by a fusion, as the block's norm makes it
        return jnp.sum(jnp.square(layer(p, x * 2)[0].astype(jnp.float32)))

    hlo = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    insts = entry_instructions(hlo)
    kernels = mosaic_calls(insts)
    assert sorted(re.search(r"jit\((_mla_attention_\w+)\)",
                            insts[name][2]).group(1)
                  for name in kernels) == ["_mla_attention_bwd",
                                           "_mla_attention_fwd"], sorted(
                                               kernels)
    kv = "bf16[%d,%d,%d,%d]" % (n_b, cfg.num_key_value_heads, seq,
                                cfg.head_dim)
    for name in kernels:
        assert insts[name][2].count(kv) >= 2, insts[name][2][:400]
    tiles = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(r"= \(?f32\[\d+,\d+,(%d,%d|1024,1024)\]"
                          % (BLOCK, BLOCK), line) and "gqa_" in line]
    assert not tiles, tiles
    for name in kernels:
        moved = copies_beside(insts, name, n_b * seq * n_h * cfg.head_dim)
        assert not moved, (name, moved)


def _prefetch(line: str) -> bool:
    """Whether a copy only moves its operand to another memory space (the
    compiler's own prefetch for the next fusion: same shape, same tiling)."""
    shapes = re.findall(r"(\w+\[[\d,]*\]\{[^}]*\})", line)[:2]
    return (line.startswith("%copy-start") and len(shapes) == 2
            and len({re.sub(r"S\(\d+\)", "", s) for s in shapes}) == 1)


@pytest.mark.parametrize("cell", ["mellum2_12b", "solar_open2_250b"])
def test_expert_layer_gradient_runs_the_grouped_kernels_alone(
        cell, one_chip, no_compile_cache, monkeypatch):
    """`jax.grad` of one `routed_moe` layer at the widths of
    mellum2_12b.train (16 of 64 experts 896 wide on a stream of 2,304, all
    131,072 pairs of 16,384 tokens in one chunk) and of
    solar_open2_250b.train (8 of 320 experts 1,280 wide on a stream of
    4,096, a chunk of 6,556 pairs on 8,704 rows, the stacks' gradients
    summed in two blocks; PR 36): Mosaic accepts
    ops/pallas_gmm.py's two kernels, they are the loops' only custom calls
    (three `_gmm_rows` in the forward loop; in the backward three more, the
    three transposed ones and three `_gmm_weights`), no `ragged-dot` is
    left, a stack reaches the transposed product as it is stored (nothing a
    stack's size is copied or transposed next to a kernel), and nothing the
    size of a chunk's narrower operand either.  The chunk's rows come back
    to their tokens by gathers (PR 34): no scatter anywhere writes rows of
    the stream's width into an array of a row a token, and neither loop's
    body holds a float32 array of the chunk's rows (what such a scatter's
    updates were)."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.models.ctx import Args
    from homebrewnlp_tpu.models.hybrid import expert_chunk
    from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
    from homebrewnlp_tpu.ops.pallas_gmm import ROW_TILE, aligned_rows
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs",
                           cell + ".json")) as f:
        raw = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = Config(raw)
    names = ("batch", "sequence", "heads", "features_per_head")
    spec = [e for part in raw["block_config"] for e in part["layer"]
            if e.startswith("routed_moe")][0].split("-")[1:]

    def layer(params, x):
        ctx = Ctx(cfg, params=params, train=params is not None)
        out = ctx.scoped("routed_moe_", LAYER_FUNCTIONS["routed_moe"],
                         Args(ctx, NT(x, names), spec))
        return out.x, ctx.collected

    x = jax.ShapeDtypeStruct(
        (cfg.train_batch_size, cfg.sequence_length, cfg.heads,
         cfg.features_per_head), jnp.bfloat16, sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in jax.eval_shape(lambda x: layer(None, x)[1],
                                         x).items()}

    def loss(p, x):
        return jnp.sum(jnp.square(layer(p, x * 2)[0].astype(jnp.float32)))

    hlo = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    assert not re.search(r" ragged-dot\(", hlo)
    held, inter = cfg.experts_held, cfg.moe_intermediate_size
    tokens = cfg.train_batch_size * cfg.sequence_length
    stream = cfg.heads * cfg.features_per_head
    topk = int([e for e in spec if e.startswith("topk")][0][len("topk"):])
    rows = aligned_rows(expert_chunk(tokens, topk, held, cfg.experts), held,
                        ROW_TILE)
    assert (tokens, stream, rows) == {
        "mellum2_12b": (16384, 2304, 135168),
        "solar_open2_250b": (8192, 4096, 8704)}[cell]
    assert not re.findall(r"= f32\[(%d|%d),%d\]\S* scatter\("
                          % (tokens, tokens + 1, stream), hlo)
    found = []
    for body in re.findall(r"^%?[\w.\-]+ \([^\n]*\{\n(.*?)^\}", hlo,
                           re.S | re.M):
        insts = instructions(body)
        if mosaic_calls(insts):
            wide = [line.strip()[:160] for _, _, line in insts.values()
                    if " = f32[%d,%d]" % (rows, stream) in line]
            assert not wide, wide
        for name in mosaic_calls(insts):
            found.append(re.search(r"jit\((_gmm_\w+)\)",
                                   insts[name][2]).group(1))
            assert "bf16[%d," % rows in insts[name][2], insts[name][2][:300]
            moved = [line for line in copies_beside(
                insts, name, min(rows, held * stream) * inter)
                if not _prefetch(line)]
            assert not moved, (name, moved)
    assert sorted(found) == ["_gmm_rows"] * 9 + ["_gmm_weights"] * 3, found


def compiled_step(cfg: Config, device):
    """`Trainer`'s whole update (gradient, clipping, the optimizer chain)
    compiled for the described chip, the state and the batch given as shapes
    laid out as `Trainer.init` and the feed lay them out."""
    from jax.sharding import NamedSharding, PartitionSpec
    from homebrewnlp_tpu.optim import Optimizer
    from homebrewnlp_tpu.parallel import make_mesh, param_shardings, spec_for
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.train.state import TrainState
    mesh = make_mesh(cfg, [device])
    trainer = Trainer(cfg, mesh)
    on = lambda names: NamedSharding(mesh, spec_for(names, mesh))
    shape = (cfg.train_batch_size, cfg.sequence_length, cfg.token_patch_size)
    tok = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on(TOKEN_NAMES))
    batch = {k: NT(tok, TOKEN_NAMES) for k in ("token_x", "token_y")}
    axes: dict = {}

    def collect():
        ctx = Ctx(cfg, params=None, seed=0, train=False)
        build(ctx, {k: NT(jnp.zeros(shape, jnp.int32), TOKEN_NAMES)
                    for k in batch})
        axes.update(ctx.axis_names)
        return ctx.collected

    abstract = jax.eval_shape(collect)
    trainer.axes, trainer.optimizer = axes, Optimizer(cfg, axes)
    shard = param_shardings(axes, mesh)
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.dtype(cfg.slice_dtype),
                                      sharding=shard[k])
              for k, v in abstract.items()}
    slot_axes = trainer.optimizer.slot_axis_names()
    slots = {name: {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=on(slot_axes[name][k]))
                    for k, v in leaf.items()}
             for name, leaf in jax.eval_shape(trainer.optimizer.init,
                                              params).items()}
    scalar = NamedSharding(mesh, PartitionSpec())
    state = TrainState(params, slots, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=scalar))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=scalar)
    with mesh:
        return trainer._make_step().lower(
            state, batch, key, *trainer.step_extra_args()).compile()


def test_solar_open2_step_fits_the_chip_and_keeps_its_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """The whole update of solar_open2_250b.train (905.76 M parameters, one
    sequence of 8,192 tokens) compiled for the described v5e: its state,
    gradients and scratch stay under 15.0 GB (at two sequences they read
    17.4 GB, which is why the cell takes one: PERF.md section 4); the delta
    rule and the attention run as their four Mosaic kernels, the attention's
    take `k`, `v` with the 2 K/V heads held here, nothing shaped like a
    score tile is left under `gqa_`, and the routed products run as the two
    grouped kernels with no `ragged-dot` left (PR 36: 13.71 GB, 13.68 as
    `ragged_dot`)."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "solar_open2_250b.json")) as f:
        raw = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = Config(raw)
    compiled = compiled_step(cfg, next(iter(one_chip.device_set)))
    memory = compiled.memory_analysis()
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert 12.5e9 < need < 15.0e9, need
    hlo = compiled.as_text()
    assert sorted(set(re.findall(r'jit\((_\w+)\)[^"]*pallas_call', hlo))) == [
        "_gmm_rows", "_gmm_weights", "_kda_chunks_bwd", "_kda_chunks_fwd",
        "_mla_attention_bwd", "_mla_attention_fwd"]
    assert not re.search(r" ragged-dot\(", hlo)
    kv = "bf16[%d,%d,%d,%d]" % (cfg.train_batch_size, cfg.num_key_value_heads,
                                cfg.sequence_length, cfg.head_dim)
    attention = [line for line in hlo.splitlines()
                 if "tpu_custom_call" in line and "_mla_attention_" in line]
    assert attention and all(line.count(kv) >= 2 for line in attention)
    tiles = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(r"= \(?f32\[\d+,\d+,(%d,%d|1024,1024)\]"
                          % (BLOCK, BLOCK), line) and "gqa_" in line]
    assert not tiles, tiles



def _keye_config() -> Config:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye_vl2_30b.json")) as f:
        return Config({k: v for k, v in json.load(f).items()
                       if k != "benchmark"})


def test_keye_vl2_step_fits_the_chip_and_runs_the_sparse_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """The whole update of keye_vl2_30b.train (659.2 M parameters, one
    sequence of 16,384 tokens) compiled for the described v5e: its state,
    gradients and scratch lie between a quarter of the chip and 95% of it
    (13.16 GB; 9.58 when the remat ran the forward kernels again); every
    sparse layer runs ops/sparse_attention.py's five Mosaic kernels (the
    selection, attention forward, its two backward
    kernels under one jit, the indexer's loss) and the experts' grouped
    kernels, no dense attention kernel and no sort under `gqa_` (the
    experts' dispatch sorts its pairs).  The part's checkpoint keeps the
    three forward kernels' outputs, so each runs once a layer: 6 calls, not
    the 12 of a remat that runs them again."""
    import homebrewnlp_tpu.ops as ops
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compiled_step(_keye_config(), next(iter(one_chip.device_set)))
    memory = compiled.memory_analysis()
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert 0.25 * 16e9 < need < 0.95 * 16e9, need
    hlo = compiled.as_text()
    assert sorted(set(re.findall(r'jit\((_\w+)\)[^"]*pallas_call', hlo))) == [
        "_attention_bwd", "_attention_fwd", "_gmm_rows", "_gmm_weights",
        "_indexer_kl", "_select"]
    calls = re.findall(r'jit\((_\w+)\)[^"]*pallas_call', "\n".join(
        line for line in hlo.splitlines() if "tpu_custom_call" in line))
    for kernel in ("_select", "_attention_fwd", "_indexer_kl"):
        assert calls.count(kernel) == 6, (kernel, calls.count(kernel))
    sorts = [line for line in hlo.splitlines()
             if re.search(r" sort\(", line) and "gqa_" in line]
    assert not sorts, sorts[:2]


@pytest.mark.parametrize("kernel", ["select", "forward", "backward", "kl"])
def test_sparse_attention_kernels_take_16384_rows_within_vmem(
        kernel, one_chip, no_compile_cache):
    """Mosaic accepts each kernel of ops/sparse_attention.py at the cell's
    shape (16,384 tokens, 32 query heads of 128 over 4 K/V heads, an indexer
    of 16 heads of 64, tiles of 512) under the 64 MiB VMEM limit: the key
    tiles are a grid axis, so nothing a kernel holds grows with the
    sequence but the indexer loss's float32 key gradient (4 MiB) and the
    selection's block of 256 rows of keys (16 MiB)."""
    from homebrewnlp_tpu.ops import sparse_attention as sa
    b, h, g, t, d, ni, di, blk = 1, 32, 4, 16384, 128, 16, 64, 512
    s = lambda shape, kind=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, kind, sharding=one_chip)
    q, kv = s((b, h, t, d)), s((b, g, t, d))
    qi, ki, w = s((b, ni, t, di)), s((b, t, di)), s((b, t, ni), jnp.float32)
    mask = s((b, t, t), jnp.int8)
    lse, lse_i = s((b, g, t, h // g), jnp.float32), s((b, t, 1), jnp.float32)
    calls = {
        "select": (lambda *a: sa.select(*a, 2048, blk, False), (qi, ki, w)),
        "forward": (lambda *a: sa._attention_fwd(*a, block=blk),
                    (q, kv, kv, mask)),
        "backward": (lambda *a: sa._attention_bwd(*a, block=blk),
                     (q, kv, kv, mask, q, lse, q)),
        "kl": (lambda *a: sa._indexer_kl(*a, block=blk),
               (q, kv, lse, qi, ki, w, mask, lse_i)),
    }
    fn, args = calls[kernel]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_kanana2_step_fits_the_chip_through_the_key_block_kernels(
        one_chip, no_compile_cache, monkeypatch):
    """The whole update of kanana2_30b.train (575.96 M parameters, one
    sequence of 32,768 tokens) compiled for the described v5e: its state,
    gradients and scratch lie between a quarter of the chip and 95% of it
    (13.00 GB; 11.64 when the remat ran the forward kernel again); every
    one of its five latent-attention layers runs ops/pallas_mla.py's
    key-block kernels (a head's keys do not fit VMEM whole at this length),
    counted as they are traced, with no call left to
    the unrolled tiles or the resident kernels, and nothing shaped like a
    score tile is left under `mla_`; the experts run as the grouped
    kernels."""
    import homebrewnlp_tpu.ops as ops
    from homebrewnlp_tpu.obs import compile_log
    from homebrewnlp_tpu.obs.registry import REGISTRY
    from homebrewnlp_tpu.ops.block_attention import WALKS
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    compile_log.install()
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kanana2_30b.json")) as f:
        cfg = Config({k: v for k, v in json.load(f).items()
                      if k != "benchmark"})
    counter = REGISTRY.counter("hbnlp_attention_path_total",
                               labelnames=("path",))
    before = {p: counter.value(path=p) for p in WALKS}
    compiled = compiled_step(cfg, next(iter(one_chip.device_set)))
    walked = {p: counter.value(path=p) - before[p] for p in WALKS}
    # the abstract trace of the initialiser and the update's own trace
    assert walked == {"resident": 0, "key_blocks": 2 * 5, "unrolled": 0}
    memory = compiled.memory_analysis()
    need = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert 0.25 * 16e9 < need < 0.95 * 16e9, need
    hlo = compiled.as_text()
    assert sorted(set(re.findall(r'jit\((_\w+)\)[^"]*pallas_call', hlo))) == [
        "_gmm_rows", "_gmm_weights", "_key_blocks_bwd", "_key_blocks_fwd"]
    calls = re.findall(r'jit\((_\w+)\)[^"]*pallas_call', "\n".join(
        line for line in hlo.splitlines() if "tpu_custom_call" in line))
    # a forward a layer (the part's checkpoint keeps its output and row
    # statistic, so the remat does not run it again); the backward as two
    # kernels (dQ, then dK and dV) under one jit
    assert calls.count("_key_blocks_fwd") == 5
    assert calls.count("_key_blocks_bwd") == 2 * 5
    tiles = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(r"= \(?f32\[\d+,\d+,(%d,%d|1024,1024)\]"
                          % (BLOCK, BLOCK), line) and "mla_" in line]
    assert not tiles, tiles


@pytest.mark.parametrize("h,g,window", [(32, 32, None), (32, 4, 1024)],
                         ids=["latent", "grouped_window"])
def test_key_block_kernels_take_32768_rows_within_vmem(h, g, window,
                                                       one_chip,
                                                       no_compile_cache):
    """Mosaic accepts the key-block kernels' forward and backward at 32,768
    tokens (the latent attention's 192 / 128 widths; grouped K/V heads of
    128 under a window), where the resident kernels' VMEM need (167 MiB)
    passes the 64 MiB limit: a cell holds `KEYS` keys and nothing grows
    with the sequence."""
    from homebrewnlp_tpu.ops import pallas_mla
    s = 32768
    d, d_v = (192, 128) if g == h else (128, 128)
    assert pallas_mla.vmem_bytes(s, d, d_v, 2) > pallas_mla.VMEM_BYTES
    shape = lambda *x: jax.ShapeDtypeStruct(x, jnp.bfloat16,
                                            sharding=one_chip)
    keys = pallas_mla.key_chunk(s)

    def loss(q, k, v):
        return jnp.sum(pallas_mla.key_block_attention(
            q, k, v, pallas_mla.BLOCK, keys, window, False
        ).astype(jnp.float32))

    hlo = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shape(1, h, s, d), shape(1, g, s, d), shape(1, g, s, d_v)
    ).compile().as_text()
    calls = re.findall(r'jit\((_\w+)\)[^"]*pallas_call', "\n".join(
        line for line in hlo.splitlines() if "tpu_custom_call" in line))
    # the forward, then the backward's two kernels (dQ; dK and dV)
    assert sorted(calls) == ["_key_blocks_bwd", "_key_blocks_bwd",
                             "_key_blocks_fwd"], calls
