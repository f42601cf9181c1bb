"""chip_smoke.py on the CPU test platform, and the compile-cache rule.

The smoke is the driver's proof that the served path starts on the
chip; here it must keep two promises that a chip run cannot check:
at a tiny shape under JAX_PLATFORMS=cpu every FUNCTIONAL check passes
(three broker processes, client processes over TCP, a ring that wraps
under trim, sealed + erasure-coded segments, three byte-exact on-disk
copies, the RS kernel vs its reference) while the DEVICE check refuses
with its own exit code and no result on stdout — there is no way to
make a CPU run pass.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra}
    # conftest's 8 virtual devices are this process's business only.
    env.pop("XLA_FLAGS", None)
    return env


def test_tiny_smoke_passes_functionally_and_refuses_the_device():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny", "--seed", "3"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    assert res.returncode == 4, (res.returncode, res.stdout[-3000:],
                                 res.stderr[-3000:])
    summary = json.loads(res.stderr.strip().splitlines()[-1])
    assert summary["functional_ok"] is True, summary
    assert summary["failures"] == []
    assert summary["device_ok"] is False and summary["ok"] is False
    assert summary["engine_device"]["platform"] == "cpu"
    assert summary["engine_device"]["append_backend"] == "xla"
    # 8 partitions x 2 batches of 32, plus three laps of a 256-slot ring.
    assert summary["messages"] == 8 * 64 + 800
    # A refused run prints no result: nothing JSON-shaped on stdout.
    assert not [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
    assert "device check REFUSED" in res.stdout


def test_smoke_deployment_is_the_benchmark_configs_cluster_block():
    """The smoke boots what the omb-1024p-100b cells boot: the config
    file's `cluster` block and topics, nothing added or changed but one
    broker per port."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "omb-1024p-100b.json")) as f:
        config = json.load(f)
    raw = chip_smoke.deployment_raw([7001, 7002, 7003])
    assert [b["port"] for b in raw.pop("brokers")] == [7001, 7002, 7003]
    assert raw.pop("topics") == config["deployment"]["topics"]
    assert raw == config["cluster"]
    # What the smoke's own checks lean on: a ring its 16,384 extra
    # messages lap three times, and a segment size it can read.
    assert 3 * raw["engine"]["slots"] <= 16384
    assert raw["segment_bytes"] == 64 << 20


_PROBE = (
    "import json, jax\n"
    "from ripplemq_tpu.utils.compile_cache import configure_compile_cache\n"
    "print(json.dumps([configure_compile_cache(),"
    " jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_persistent_cache_min_compile_time_secs]))\n"
)


def _probe(**extra) -> subprocess.Popen:
    """The helper's verdict in a fresh process that may own a device
    (JAX_PLATFORMS unset; it configures, it never initialises a
    backend)."""
    env = _env(**extra)
    for var in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        if var not in extra:
            env.pop(var, None)
    return subprocess.Popen([sys.executable, "-c", _PROBE], cwd=REPO,
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _verdict(proc: subprocess.Popen) -> list:
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside_or_at_one_fixed_path(tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    placed = _probe(JAX_COMPILATION_CACHE_DIR=outside,
                    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5")
    first, second = _probe(), _probe()
    pinned = _probe(JAX_PLATFORMS="cpu")
    # Variable set: no directory is configured in code — JAX reads it
    # (and a threshold set from outside is left alone too).
    assert _verdict(placed) == [None, outside, 2.5]
    # Unset: two separate processes agree on ONE in-checkout directory
    # (the path is part of the cache key — a moving directory never
    # hits), with the threshold that lets the engine programs in.
    want = os.path.join(REPO, ".jax_cache")
    assert _verdict(first) == [want, want, 0.0]
    assert _verdict(second) == [want, want, 0.0]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # A process pinned to the CPU backend owns no device: left alone.
    assert _verdict(pinned) == [None, None, 1.0]
