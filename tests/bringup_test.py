"""Bring-up invariants (PR 21): the compile cache is placed from outside, no
fallback hides the device on the main path, and ``chip_smoke.py``'s control
flow — rehearsed here at a toy width by calling its phase functions; the
script itself runs on a TPU only."""
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

from .backend import text_batch, tiny_config  # noqa: E402


def _run(code_or_path, env=None, unset=(), cwd=REPO, timeout=300):
    argv = ([sys.executable, code_or_path] if os.path.exists(code_or_path)
            else [sys.executable, "-c", code_or_path])
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    for name in unset:
        full_env.pop(name, None)
    return subprocess.run(argv, env=full_env, cwd=cwd, timeout=timeout,
                          capture_output=True, text=True)


# -- compile cache placed from outside ----------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from homebrewnlp_tpu.utils import enable_compilation_cache\n"
    "used = enable_compilation_cache()\n"
    "import json; print(json.dumps([before, used, "
    "jax.config.jax_compilation_cache_dir]))\n")


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own reading of it stands and the
    helper sets no directory in code."""
    where = str(tmp_path / "outside")
    out = _run(_CACHE_PROBE, env={"JAX_COMPILATION_CACHE_DIR": where})
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == [where, where, where]


def test_cache_dir_defaults_to_the_checkout():
    out = _run(_CACHE_PROBE, unset=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert json.loads(out.stdout.splitlines()[-1]) == [None, want, want]


def test_one_site_sets_the_cache_directory():
    """Exactly one ``jax_compilation_cache_dir`` update in the tree, and the
    knob / variable that used to place the cache are gone."""
    update = re.compile(r"update\(\s*[\"']jax_compilation_" + r"cache_dir")
    gone = re.compile("HBNLP_COMPILATION_" + "CACHE_DIR|compilation_" +
                      r"cache_dir\s*=")
    sites, leftovers = [], []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (
            ".git", "runs", "datasets", "chiprun_out", ".jax_cache",
            "__pycache__")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                text = f.read()
            sites += [path] * len(update.findall(text))
            if gone.search(text):
                leftovers.append(path)
    assert sites == [os.path.join(REPO, "homebrewnlp_tpu", "utils",
                                  "__init__.py")]
    assert leftovers == []
    assert not hasattr(tiny_config(), "compilation_cache_dir")


# -- no fallback that hides the device ----------------------------------------

def test_interpret_mode_on_cpu_only(monkeypatch):
    from homebrewnlp_tpu.ops import pallas_interpret
    assert pallas_interpret() is True  # this suite runs on the cpu backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        pallas_interpret()


def test_peak_flops_exact_kind_or_error():
    from homebrewnlp_tpu.devices import resolve_device
    from homebrewnlp_tpu.train.flops import peak_flops
    assert peak_flops("TPU v5 lite") == 197e12  # what the runtime prints
    assert peak_flops("cpu") is None and resolve_device("cpu") is None
    for unknown in ("TPU v5", "TPU v5e", "TPU v7x", "tpu v5 lite", ""):
        # no substring match: a neighbour's peak is not a default
        with pytest.raises(ValueError, match="unknown device kind"):
            peak_flops(unknown)
        with pytest.raises(ValueError, match="unknown device kind"):
            resolve_device(unknown)


def test_mesh_that_leaves_accelerators_out_is_an_error(eight_devices):
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = tiny_config(heads=2, train_batch_size=2)  # data axis 4, batch 2

    class FakeChip:
        platform = "tpu"

    with pytest.raises(ValueError, match="4 device.s. left unused"):
        make_mesh(cfg, devices=[FakeChip()] * 8)
    # the CPU test mesh only warns, and runs on the devices it can use
    assert make_mesh(cfg, devices=eight_devices).size == 4


def test_step_raises_instead_of_recompiling(eight_devices):
    """Arguments that no longer match the kept AOT executable are an error,
    not a silent second compile through jit."""
    from homebrewnlp_tpu.train import Trainer
    cfg = tiny_config()
    trainer = Trainer(cfg)
    batch = text_batch(cfg)
    state = trainer.init(batch)
    trainer.step_cost_analysis(state, batch)
    wider = text_batch(tiny_config(train_batch_size=4))
    with pytest.raises((TypeError, ValueError)):
        trainer.step(state, wider, jax.random.key(0))
    assert trainer._compiled is not None


def test_bench_refuses_a_cpu_and_fails_on_error_rows():
    out = _run(os.path.join(REPO, "bench.py"))
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr and not out.stdout.strip()
    import bench
    record = {"workloads": {"a": {"value": 1.0, "profile": {"error": "x"}},
                            "b": {"error": "boom"}, "c": {"error_rate": 0.0}},
              "numerics_guard": {"pass": True}}
    assert bench._rows_with_error(record) == ["workloads/a/profile",
                                              "workloads/b"]


def test_native_library_name_follows_the_source_hash():
    from homebrewnlp_tpu import native
    with open(os.path.join(REPO, "native", "hbnlp_native.cc"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    assert os.path.basename(native.lib_path()) == (
        f"libhbnlp_native.{digest}.so")
    assert native.available()
    built = [n for n in os.listdir(os.path.join(REPO, "native"))
             if n.startswith("libhbnlp_native") and n.endswith(".so")]
    assert built == [os.path.basename(native.lib_path())]  # no stale binary


# -- one process for each chip ------------------------------------------------

def test_corpus_tools_never_initialise_a_backend():
    """bench's guard shells out to build_corpus -> text2tfrecord's pool after
    the parent holds the chip; those children import the package (and with
    it jax) but must never open a device."""
    out = _run("import sys; sys.argv = ['x']; sys.path.insert(0, 'tools')\n"
               "import build_corpus, text2tfrecord, homebrewnlp_tpu.native\n"
               "from jax._src import xla_bridge\n"
               "print(xla_bridge.backends_are_initialized())\n")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "False"


def test_graftserve_gives_every_replica_its_own_chip():
    import graftserve
    tpu = {"JAX_PLATFORMS": "tpu,cpu"}
    assert graftserve.replica_chip_envs(3, {"JAX_PLATFORMS": "cpu"},
                                        n_chips=4) == [{}, {}, {}]
    assert graftserve.replica_chip_envs(2, tpu, n_chips=0) == [{}, {}]
    envs = graftserve.replica_chip_envs(4, tpu, n_chips=4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    with pytest.raises(SystemExit, match="2 replicas need 2 chips"):
        graftserve.replica_chip_envs(2, tpu, n_chips=1)


# -- chip_smoke.py ------------------------------------------------------------

def test_chip_smoke_refuses_a_cpu():
    out = _run(os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode == 2
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(str(tmp_path / "chip_smoke.py"), cwd=str(tmp_path))
    assert out.returncode != 0 and '"ok"' not in out.stdout


TOY = dict(depth=2, features_per_head=32, sequence_length=64)


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    import chip_smoke
    root = tmp_path_factory.mktemp("smoke")
    glob = chip_smoke.write_dataset(str(root / "data"))
    return chip_smoke, root, [{"path": glob, "type": "text", "weight": 1}]


def test_smoke_kernel_phase_at_toy_width():
    import chip_smoke
    result = chip_smoke.phase_kernel(2, 128, 2, 128)
    # on the cpu the interpreter runs it and no TPU custom call is lowered
    assert result["mosaic"] is False and result["tpu_custom_calls"] == 0
    assert result["max_rel_err"] <= chip_smoke.KERNEL_REL_TOL


def test_smoke_train_phase_at_toy_width(smoke, eight_devices):
    from homebrewnlp_tpu.utils import one_chip_config
    chip_smoke, root, dataset = smoke
    cfg = one_chip_config(chip_smoke.FLAGSHIP, dataset_configs=dataset,
                          model_path=str(root / "train"), **TOY)
    result = chip_smoke.phase_train(cfg, 16, 0.0)
    assert result["data_source"] == "dataset_files"
    assert len(result["losses"]) == 16 and result["n_devices"] == 8
    # an impossible margin fails the phase on the rows already written
    with pytest.raises(chip_smoke.SmokeFailure, match="loss did not fall"):
        chip_smoke.check_train_run(cfg, 16, 10.0)


def test_smoke_train_phase_refuses_the_synthetic_fallback(smoke,
                                                          eight_devices):
    from homebrewnlp_tpu.utils import one_chip_config
    chip_smoke, root, _ = smoke
    nowhere = [{"path": str(root / "nowhere" / "*"), "type": "text",
                "weight": 1}]
    cfg = one_chip_config(chip_smoke.FLAGSHIP, dataset_configs=nowhere,
                          model_path=str(root / "fallback"), **TOY)
    with pytest.raises(chip_smoke.SmokeFailure, match="'synthetic'"):
        chip_smoke.phase_train(cfg, 1, 0.0)


def test_smoke_serve_phase_at_toy_width(smoke):
    from homebrewnlp_tpu.utils import one_chip_config
    chip_smoke, root, _ = smoke
    cfg = one_chip_config(chip_smoke.FLAGSHIP, train=False,
                          train_batch_size=1,
                          serve_max_batch=chip_smoke.SERVE_LANES,
                          model_path=str(root / "serve"), **TOY)
    result = chip_smoke.phase_serve(cfg)
    assert result["engine"] == "BatchEngine" and result["requests"] == 5
    # a config the KV cache cannot serve stays serialized: the smoke refuses
    serial = one_chip_config(chip_smoke.FLAGSHIP, train=False,
                             train_batch_size=1, serve_max_batch=1,
                             model_path=str(root / "serial"), **TOY)
    with pytest.raises(chip_smoke.SmokeFailure, match="BatchEngine"):
        chip_smoke.phase_serve(serial)
