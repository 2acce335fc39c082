"""One owner per chip, and no quiet stand-ins (ISSUE 21).

The runtime counts chips without opening a JAX backend; a worker granted
TPU resources is pinned to the TPU and every other worker to the CPU; the
compile cache directory comes from outside; the native store says why when
it gives way; `chip_smoke.py` can be rehearsed on the CPU and never calls a
CPU run a chip run. Nothing here needs a chip.
"""
import json
import logging
import os
import subprocess
import sys
import textwrap

import pytest

import ray_tpu
from ray_tpu.core import resources as res_mod
from ray_tpu.util import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- counting chips -------------------------------------------------------

def _fake_dev(monkeypatch, listing):
    def listdir(path):
        if path not in listing:
            raise FileNotFoundError(path)
        return listing[path]
    monkeypatch.setattr(res_mod.os, "listdir", listdir)
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)


@pytest.mark.parametrize("platforms,listing,want", [
    # v5e: one VFIO group per chip next to the vfio control node
    ("tpu,cpu", {"/dev": ["null", "vfio"], "/dev/vfio": ["0", "vfio"]}, 1),
    ("", {"/dev": ["vfio"], "/dev/vfio": ["0", "1", "2", "3", "vfio"]}, 4),
    # v4 and older: /dev/accel<N>
    ("", {"/dev": ["accel0", "accel1", "accelerometer", "null"]}, 2),
    ("", {"/dev": ["null"]}, 0),
    # a tree pinned off the TPU has no chips to hand out
    ("cpu", {"/dev": ["vfio"], "/dev/vfio": ["0", "vfio"]}, 0),
])
def test_chips_are_counted_from_device_files(monkeypatch, platforms,
                                             listing, want):
    _fake_dev(monkeypatch, listing)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert res_mod._detect_tpu_chips() == want


def test_chip_count_override_and_unreadable_dev(monkeypatch):
    _fake_dev(monkeypatch, {})
    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setenv("RAY_TPU_CHIPS", "8")
    assert res_mod._detect_tpu_chips() == 8

    def denied(path):
        raise PermissionError(path)
    monkeypatch.delenv("RAY_TPU_CHIPS")
    monkeypatch.setattr(res_mod.os, "listdir", denied)
    with pytest.raises(PermissionError):   # never folded into "0 chips"
        res_mod._detect_tpu_chips()


def test_init_leaves_the_driver_without_a_jax_backend():
    """Fresh process, ambient platform unset as on a TPU host: init(), the
    node agent's module and its resource detection open no backend."""
    code = textwrap.dedent("""
        import sys
        import ray_tpu
        ray_tpu.init(num_cpus=2)
        import ray_tpu.core.node
        from ray_tpu.core.resources import detect_node_resources
        detect_node_resources()
        jax = sys.modules.get("jax")
        up = (jax is not None
              and jax._src.xla_bridge.backends_are_initialized())
        ray_tpu.shutdown()
        print("BACKEND_UP" if up else "NO_BACKEND")
    """)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd="/tmp",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "NO_BACKEND"


# ---- who is pinned where --------------------------------------------------

class _EnvProbe:
    """Stands in for LLMServer: reports its worker's environment."""

    def __init__(self, model_factory, engine_config=None, tokenizer=None,
                 cached_prefixes=None):
        pass

    def __call__(self, body):
        return {"platforms": os.environ.get("JAX_PLATFORMS"),
                "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                "tpu_ids": ray_tpu.get_tpu_ids()}


def test_llm_replica_with_a_chip_request_is_pinned_to_the_tpu():
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_deployment
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        with_chip = serve.run(build_llm_deployment(
            None, server_cls=_EnvProbe, name="WithChip",
            ray_actor_options={"num_tpus": 1}), name="a",
            route_prefix="/a")
        without = serve.run(build_llm_deployment(
            None, server_cls=_EnvProbe, name="NoChip"), name="b",
            route_prefix="/b")
        got = with_chip.remote({}).result(timeout_s=30)
        assert got["platforms"] == "tpu,cpu"
        assert got["cache"]            # placed from outside, never unset
        assert got["tpu_ids"] == [0]
        assert without.remote({}).result(timeout_s=30) == {
            "platforms": "cpu",
            "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "tpu_ids": []}
        capable = [w for w in state.list_workers() if w["tpu_capable"]]
        assert len(capable) == 1
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_actor_granted_a_chip_fails_on_another_platform(monkeypatch):
    """worker._check_chip_owner: an actor that brought up a backend other
    than the TPU fails its constructor; one that never touched jax is
    left alone."""
    import jax

    from ray_tpu.core import worker
    jax.devices()                      # the test process is on the CPU
    with pytest.raises(RuntimeError, match="granted TPU resources"):
        worker._check_chip_owner()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    worker._check_chip_owner()
    monkeypatch.delitem(sys.modules, "jax")
    worker._check_chip_owner()


# ---- the compile cache is placed from outside -----------------------------

def test_compile_cache_dir_comes_from_the_environment(monkeypatch):
    import jax
    set_dirs = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax.config, "update",
        lambda key, value: set_dirs.append((key, value)))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jaxenv.enable_compile_cache()
    assert set_dirs == []              # jax's own reading stands
    env = jaxenv.subprocess_env_tpu({"JAX_COMPILATION_CACHE_DIR": "/x"})
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/x"

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    jaxenv.enable_compile_cache()
    fixed = os.path.join(REPO, ".jax_cache")
    assert set_dirs == [("jax_compilation_cache_dir", fixed)]
    assert jaxenv.subprocess_env_tpu({}) == {
        "JAX_PLATFORMS": "tpu,cpu", "JAX_COMPILATION_CACHE_DIR": fixed}

    set_dirs.clear()                   # a CPU process is left alone
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    jaxenv.enable_compile_cache()
    assert set_dirs == []


# ---- the native store is built from source, and says when it is not ------

def test_native_library_is_named_by_its_source(tmp_path, monkeypatch):
    from ray_tpu._native import build
    src = tmp_path / "tiny.cc"
    src.write_text('extern "C" int answer() { return 41; }\n')
    monkeypatch.setattr(build, "_HERE", str(tmp_path))
    monkeypatch.setattr(build, "_BUILD_DIR", str(tmp_path / "build"))
    first = build.build_library("tiny")
    os.utime(src, (0, 0))              # file times decide nothing
    assert build.build_library("tiny") == first
    src.write_text('extern "C" int answer() { return 42; }\n')
    second = build.build_library("tiny")
    assert second != first and os.path.exists(second)


def test_store_fallback_logs_the_reason(monkeypatch, caplog):
    from ray_tpu._native import store_binding
    from ray_tpu.core import object_store

    def no_compiler(name):
        raise FileNotFoundError("g++")
    monkeypatch.setattr(store_binding, "_lib", None)
    monkeypatch.setattr(store_binding, "build_library", no_compiler)
    with caplog.at_level(logging.WARNING, "ray_tpu.core.object_store"):
        store = object_store.make_store(1 << 20, is_owner=True)
    try:
        assert type(store).__name__ == "ShmStore"
        assert "g++" in caplog.text and "ShmStore" in caplog.text
    finally:
        store.shutdown()


# ---- the trainer builds the mesh it was given, or raises -------------------

def test_spmd_trainer_refuses_a_mesh_that_does_not_fit():
    import jax

    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import SpmdTrainer, SpmdTrainerConfig
    assert len(jax.devices()) == 8
    trainer = SpmdTrainer(
        SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(fsdp=2)),
        lambda: iter(()))
    with pytest.raises(ValueError, match="needs 2 devices, got 8"):
        trainer.fit()


# ---- chip_smoke.py ---------------------------------------------------------

def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd="/tmp", capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_fast_without_a_chip():
    out = _smoke()
    assert out.returncode != 0
    assert '"platform": "tpu"' not in out.stdout
    assert json.loads(out.stdout.splitlines()[-1])["ok"] is False


# ~30 s, and tier-1 has no room for it (ROADMAP C8). Builders run the
# rehearsal before any chip time (verify skill) and the driver runs the
# script itself on the chip, so it cannot rot unseen.
@pytest.mark.slow
def test_chip_smoke_rehearsal_on_the_cpu_is_never_a_chip_result():
    out = _smoke("--rehearse")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    reports = [json.loads(line) for line in out.stdout.splitlines()]
    assert [r.get("phase") for r in reports[:-1]] == [
        "kernels", "serve", "train"]
    assert all(r["ok"] for r in reports)
    assert reports[1]["checks"]["driver_holds_no_backend"]
    assert reports[1]["requests"] >= 8
    assert reports[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert '"platform": "tpu"' not in out.stdout
