"""The program store (utils/program_store.py): a loaded round program
is the built one, the key holds everything a program is made from, a
bad artifact is a logged miss and never an exception or a stale
program, and a process pinned to the CPU backend is left alone.

All on the CPU backend with a `tmp_path` store handed to the wrapper
(on load XLA:CPU writes long `cpu_aot_loader.cc` lines to stderr: noise
of that backend). The chip's side is `chip_smoke.py` and the benchmark.
"""

import contextlib
import dataclasses
import json
import logging
import os
import pickle
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

from ripplemq_tpu.core.state import StepInput
from ripplemq_tpu.parallel.engine import make_local_fns
from ripplemq_tpu.utils import compile_cache, program_store
from ripplemq_tpu.utils.program_store import ProgramStore

from tests.helpers import make_input, small_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = small_cfg(partitions=8, replicas=3, slots=64, max_batch=8)
ALIVE = np.ones((CFG.partitions, CFG.replicas), bool)
K = 3  # chain depth of the chained cases


def sparse_round(rng, bucket: int, extents: bool = True):
    """One seeded round in the active-set form: up to `bucket`
    partitions append 1-3 messages each."""
    parts = rng.choice(CFG.partitions, size=int(rng.integers(1, bucket + 1)),
                       replace=False)
    appends = {int(p): [bytes(rng.integers(0, 256, int(rng.integers(1, 25)),
                                           dtype=np.uint8))
                        for _ in range(int(rng.integers(1, 4)))]
               for p in parts}
    inp = make_input(CFG, appends=appends, leader=0, term=1)
    entries = np.asarray(inp.entries)
    ec = np.zeros((bucket,) + entries.shape[1:], np.uint8)
    ids = np.full((bucket,), -1, np.int32)
    for a, p in enumerate(sorted(appends)):
        ec[a], ids[a] = entries[p], p
    inp = inp._replace(entries=np.zeros((1, 1, CFG.slot_bytes), np.uint8))
    if not extents:
        inp = inp._replace(extents=None)
    return inp, ec, ids


def chained_round(rng, bucket: int):
    rounds = [sparse_round(rng, bucket) for _ in range(K)]
    inputs = StepInput(*[np.stack([np.asarray(getattr(r[0], f))
                                   for r in rounds])
                         for f in StepInput._fields])
    return (inputs, np.stack([r[1] for r in rounds]),
            np.stack([r[2] for r in rounds]))


def run_rounds(fns, seed: int, bucket: int, chained: bool, n: int = 4):
    """State and outputs after `n` seeded launches; every launch must
    have taken (donated) the state it was given."""
    rng = np.random.default_rng(seed)
    state, outs = fns.init(), []
    for _ in range(n):
        if chained:
            new, out = fns.step_many_sparse(
                state, *chained_round(rng, bucket), ALIVE)
        else:
            new, out = fns.step_sparse(state, *sparse_round(rng, bucket),
                                       ALIVE)
        assert state.log_data.is_deleted(), "state not donated"
        state = new
        outs.append(jax.tree.map(np.asarray, out))
    return jax.tree.map(np.asarray, state), outs


def assert_same(a, b) -> None:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def artifacts(directory) -> list[str]:
    return sorted(os.path.join(directory, f) for f in os.listdir(directory))


@contextlib.contextmanager
def listening(*loggers: str):
    """The messages logged under `loggers` at WARNING and above, taken by
    a handler on those loggers themselves: `configure_logging` (any
    in-process broker test that ran earlier in this worker) stops the
    `ripplemq` logger's propagation, and `caplog` listens at the root."""
    said: list[tuple[str, str]] = []

    class Take(logging.Handler):
        def emit(self, record: logging.LogRecord) -> None:
            said.append((record.name, record.getMessage()))

    take = Take(level=logging.WARNING)
    held = [logging.getLogger(name) for name in loggers]
    for lg in held:
        lg.addHandler(take)
    try:
        yield said
    finally:
        for lg in held:
            lg.removeHandler(take)


# ------------------------------------------------------------------ (a)

@pytest.mark.parametrize("chained", [False, True], ids=["single", "chained"])
@pytest.mark.parametrize("bucket", [2, 4])
def test_loaded_program_is_the_traced_one(tmp_path, bucket, chained):
    seed = 1000 * bucket + chained
    want = run_rounds(make_local_fns(CFG), seed, bucket, chained)
    first = ProgramStore(str(tmp_path))
    built = run_rounds(make_local_fns(CFG, first), seed, bucket, chained)
    assert (first.loaded, first.built) == (0, 1)
    assert_same(built, want)
    assert len(artifacts(tmp_path)) == 1
    second = ProgramStore(str(tmp_path))
    loaded = run_rounds(make_local_fns(CFG, second), seed, bucket, chained)
    assert (second.loaded, second.built) == (1, 0)
    assert_same(loaded, want)
    assert np.asarray(want[0].log_data).any(), "nothing was appended"


def test_loaded_vote_is_the_traced_one(tmp_path):
    def elect(fns):
        cand = np.arange(CFG.partitions, dtype=np.int32) % CFG.replicas
        state = fns.init()
        new, elected, votes = fns.vote(
            state, cand, np.full((CFG.partitions,), 3, np.int32), ALIVE)
        assert state.log_data.is_deleted()
        return jax.tree.map(np.asarray, (new, elected, votes))

    want = elect(make_local_fns(CFG))
    first, second = ProgramStore(str(tmp_path)), ProgramStore(str(tmp_path))
    assert_same(elect(make_local_fns(CFG, first)), want)
    assert_same(elect(make_local_fns(CFG, second)), want)
    assert (first.loaded, first.built) == (0, 1)
    assert (second.loaded, second.built) == (1, 0)
    assert want[1].all()


# ------------------------------------------------------------------ (b)

def key_of(cfg=CFG, bucket=2, **env_over) -> str:
    args = (make_local_fns(cfg).init(),
            *sparse_round(np.random.default_rng(0), bucket), ALIVE)
    backend = env_over.pop("append_backend", "xla")
    env = dict(program_store.environment(), **env_over)
    return program_store.program_key(
        "_step_sparse_j", repr(program_store.signature(args)), cfg, backend,
        env)


@pytest.mark.parametrize("what", [
    "source", "config", "bucket", "jax", "jaxlib", "platform_version",
    "device_kind", "device_count", "append_backend"])
def test_key_changes_with_what_a_program_is_made_from(tmp_path, what):
    base = key_of()
    assert key_of() == base
    if what == "source":
        # One byte of one file of a package tree moves the digest, and
        # the digest moves the key.
        root = tmp_path / "pkg"
        shutil.copytree(os.path.join(REPO, "ripplemq_tpu", "ops"), root,
                        ignore=shutil.ignore_patterns("__pycache__"))
        before = program_store.source_digest(str(root))
        assert program_store.source_digest(str(root)) == before
        with open(root / "append.py", "r+b") as f:
            byte = f.read(1)
            f.seek(0)
            f.write(bytes([byte[0] ^ 1]))
        after = program_store.source_digest(str(root))
        assert after != before
        assert key_of(source=after) != key_of(source=before)
        assert len(program_store.environment()["source"]) == 64
    elif what == "config":
        for field in dataclasses.fields(CFG):
            value = getattr(CFG, field.name)
            if isinstance(value, bool):
                other = not value
            elif isinstance(value, int):
                other = value + 1
            else:
                continue
            env = program_store.environment()
            assert program_store.program_key("f", "sig", CFG, "xla", env) != \
                program_store.program_key(
                    "f", "sig", _replace_unchecked(CFG, field.name, other),
                    "xla", env), field.name
    elif what == "bucket":
        assert key_of(bucket=4) != base
    else:
        assert key_of(**{what: "another"}) != base


def _replace_unchecked(cfg, name, value):
    """A copy of `cfg` with one field changed, past __post_init__'s
    checks: the key has to see every field, valid neighbour or not."""
    other = object.__new__(type(cfg))
    for f in dataclasses.fields(cfg):
        object.__setattr__(other, f.name,
                           value if f.name == name else getattr(cfg, f.name))
    return other


_KEY_PROBE = (
    "from tests import test_program_store as t\n"
    "print(t.key_of())\n"
)


def test_key_is_the_same_in_two_processes():
    def probe(hashseed: str) -> subprocess.Popen:
        env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO)
        return subprocess.Popen([sys.executable, "-c", _KEY_PROBE], cwd=REPO,
                                env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)

    procs = [probe("1"), probe("2")]
    keys = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err[-2000:]
        keys.append(out.strip().splitlines()[-1])
    assert keys[0] == keys[1] == key_of()
    assert len(keys[0]) == 64


# ------------------------------------------------------------------ (c)

def _body(path: str) -> dict:
    with open(path, "rb") as f:
        raw = f.read()
    return pickle.loads(raw[len(program_store._MAGIC) + 32:])


def _truncate(path: str, tmp_path) -> None:
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)


def _garbage(path: str, tmp_path) -> None:
    with open(path, "wb") as f:
        f.write(np.random.default_rng(5).bytes(4096))


def _wrong_key(path: str, tmp_path) -> None:
    """A whole artifact of ANOTHER program (a config with other shapes)
    filed at this program's path: loading it would be the stale
    program."""
    other_cfg = dataclasses.replace(CFG, partitions=CFG.partitions * 2)
    other_dir = tmp_path / "other"
    fns = make_local_fns(other_cfg, ProgramStore(str(other_dir)))
    alive = np.ones((other_cfg.partitions, other_cfg.replicas), bool)
    inp = make_input(other_cfg, leader=0, term=1)._replace(
        entries=np.zeros((1, 1, other_cfg.slot_bytes), np.uint8))
    fns.step_sparse(fns.init(), inp,
                    np.zeros((2, other_cfg.max_batch, other_cfg.slot_bytes),
                             np.uint8),
                    np.full((2,), -1, np.int32), alive)
    (other,) = artifacts(other_dir)
    shutil.copyfile(other, path)


def _bad_payload(path: str, tmp_path) -> None:
    body = _body(path)
    program_store.write_artifact(
        path, body["key"], dict(body, payload=body["payload"][:1000]))


@pytest.mark.parametrize("spoil", [_truncate, _garbage, _wrong_key,
                                   _bad_payload],
                         ids=["truncated", "garbage", "wrong-key",
                              "bad-payload"])
def test_bad_artifact_is_a_logged_miss(tmp_path, spoil):
    store_dir = tmp_path / "store"
    want = run_rounds(make_local_fns(CFG), 7, 2, False)
    run_rounds(make_local_fns(CFG, ProgramStore(str(store_dir))), 7, 2, False)
    (path,) = artifacts(store_dir)
    key = _body(path)["key"]
    spoil(path, tmp_path)
    assert program_store.read_artifact(path, key) is None or \
        spoil is _bad_payload
    store = ProgramStore(str(store_dir))
    with listening("ripplemq.programs") as heard:
        got = run_rounds(make_local_fns(CFG, store), 7, 2, False)
    assert_same(got, want)
    assert (store.loaded, store.built) == (0, 1)
    said = [m for _, m in heard]
    assert any("rebuilding" in m and path in m for m in said), said
    assert any(m.startswith("built _step_sparse_j bucket 2") for m in said)
    # Rewritten whole: the next process loads it.
    assert artifacts(store_dir) == [path]
    assert program_store.read_artifact(path, key) is not None
    after = ProgramStore(str(store_dir))
    assert_same(run_rounds(make_local_fns(CFG, after), 7, 2, False), want)
    assert (after.loaded, after.built) == (1, 0)


def test_unwritable_store_builds_and_serves(tmp_path):
    """A directory that cannot be made (a file is in its way) costs the
    artifact, not the call."""
    blocker = tmp_path / "file"
    blocker.write_text("in the way")
    store = ProgramStore(str(blocker / "programs"))
    with listening("ripplemq.programs") as heard:
        got = run_rounds(make_local_fns(CFG, store), 7, 2, False)
    assert_same(got, run_rounds(make_local_fns(CFG), 7, 2, False))
    assert any("not written" in m for _, m in heard)


# ------------------------------------------------------------------ (d)

@pytest.mark.parametrize("unseen", ["extents-none", "python-scalar"])
def test_unseen_argument_tree_is_built_as_jit_builds_it(tmp_path, unseen):
    plain, store = make_local_fns(CFG), ProgramStore(str(tmp_path))
    fns = make_local_fns(CFG, store)
    # What the store has seen: the warmed tree, extents an array.
    fns.step_sparse(fns.init(), *sparse_round(np.random.default_rng(0), 2),
                    ALIVE)
    assert (store.loaded, store.built) == (0, 1)
    if unseen == "extents-none":
        # A hand-built input leaves extents=None: another tree, so
        # another program, traced and built on the spot.
        def call(f):
            rng = np.random.default_rng(3)
            return f.step_sparse(f.init(),
                                 *sparse_round(rng, 2, extents=False), ALIVE)

        assert_same(jax.tree.map(np.asarray, call(fns)),
                    jax.tree.map(np.asarray, call(plain)))
        assert (store.loaded, store.built) == (0, 2)
    else:
        # A leaf with no shape is none of the store's business: the
        # call is jit's, nothing is counted or written.
        before = artifacts(tmp_path)

        def call(f):
            inp, ec, ids = sparse_round(np.random.default_rng(3), 2)
            return f.step_sparse(f.init(), inp._replace(leader=0), ec, ids,
                                 ALIVE)

        try:
            want = jax.tree.map(np.asarray, call(plain))
        except Exception as e:  # then the wrapper raises the same
            with pytest.raises(type(e)):
                call(fns)
        else:
            assert_same(jax.tree.map(np.asarray, call(fns)), want)
        assert (store.loaded, store.built) == (0, 1)
        assert artifacts(tmp_path) == before


# ------------------------------------------------------------------ (e)

_RACER = (
    "import os, sys, time\n"
    "from ripplemq_tpu.utils import program_store as ps\n"
    "path, go, me = sys.argv[1], sys.argv[2], int(sys.argv[3])\n"
    "body = {'payload': bytes([me]) * (8 << 20), 'who': me}\n"
    "while not os.path.exists(go):\n"
    "    time.sleep(0.001)\n"
    "for _ in range(5):\n"
    "    ps.write_artifact(path, 'the-key', body)\n"
)


def test_racing_writers_leave_one_whole_file(tmp_path):
    path = str(tmp_path / "programs" / "raced.prog")
    go = str(tmp_path / "go")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", _RACER, path, go,
                               str(i)], cwd=REPO, env=env,
                              stderr=subprocess.PIPE, text=True)
             for i in (1, 2, 3)]
    open(go, "w").close()
    for p in procs:
        _, err = p.communicate(timeout=180)
        assert p.returncode == 0, err[-2000:]
    assert os.listdir(os.path.dirname(path)) == ["raced.prog"]
    body = program_store.read_artifact(path, "the-key")
    assert body is not None and body["who"] in (1, 2, 3)
    assert body["payload"] == bytes([body["who"]]) * (8 << 20)


# ------------------------------------------------------------------ (f)

@pytest.mark.parametrize("placed", ["pinned-to-cpu", "from-outside",
                                    "in-the-checkout"])
def test_store_follows_the_compile_cache_rule(tmp_path, monkeypatch, placed):
    checkout = str(tmp_path / "checkout-cache")
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", checkout)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    if placed == "pinned-to-cpu":
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert program_store.default_directory() is None
        # No store: the jitted function itself, nothing counted, and a
        # round through the default binding writes nothing anywhere.
        f = jax.jit(lambda x: x)
        assert ProgramStore(None).wrap(f, CFG, "xla") is f
        run_rounds(make_local_fns(CFG), 1, 2, True)
        assert not os.path.exists(checkout)
        assert os.listdir(tmp_path) == []
    elif placed == "from-outside":
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        outside = str(tmp_path / "outside")
        monkeypatch.setenv(compile_cache.ENV_VAR, outside)
        assert program_store.default_directory() == \
            os.path.join(outside, "programs")
    else:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert program_store.default_directory() == \
            os.path.join(checkout, "programs")
        store = ProgramStore(program_store.default_directory())
        run_rounds(make_local_fns(CFG, store), 1, 2, False)
        assert len(artifacts(os.path.join(checkout, "programs"))) == 1


# ------------------------------------------------- the order of a boot

def test_warm_builds_read_many_last_and_through_jit(tmp_path, monkeypatch):
    """`benchmarks/run.py` opens a run's window only when broker 0's
    compile log shows WARM_LAST = `jit(_read_many)` compiled or fetched
    (`warm_over`), and otherwise waits WARM_WAIT_S = 60 s - which would
    land on `setup_s`. A program loaded from the store writes no such
    line, so `_read_many` stays on `jit`, and last: every round program
    is in place when its line appears, built or loaded."""
    from ripplemq_tpu.broker.dataplane import DataPlane
    from ripplemq_tpu.storage.memstore import MemoryRoundStore

    with open(os.path.join(REPO, "benchmarks", "run.py")) as f:
        assert 'WARM_LAST = "jit(_read_many)"' in f.read()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_log_compiles", True)
    try:
        boots = []
        for _ in range(2):
            dp = DataPlane(CFG, mode="local", store=MemoryRoundStore())
            # The `jax` logger is where the benchmark's launcher listens.
            with listening("jax", "ripplemq.programs") as heard:
                dp.warm(dp.all_buckets())
            compiled = [m.split(" with ")[0] for name, m in heard
                        if name.startswith("jax")
                        and m.startswith("Compiling ")]
            ours = [re.match(r"(built|loaded) (\S+ bucket \d+)", m).groups()
                    for name, m in heard if name == "ripplemq.programs"]
            boots.append((compiled, ours, dp.device_stats(),
                          dp.metrics.snapshot()["counters"]))
            dp.stop()
    finally:
        jax.config.update("jax_log_compiles", False)
    n = 2 * len(dp.all_buckets())
    (cold, cold_ours, cold_dev, cold_m), (warm, warm_ours, warm_dev, warm_m) \
        = boots
    # First boot: every round program compiled, then _read_many.
    assert cold[-1] == "Compiling jit(_read_many)"
    assert sum("_step" in c for c in cold) == n
    assert [o[0] for o in cold_ours] == ["built"] * n
    assert [o[1] for o in cold_ours] == [
        f"{name} bucket {a}" for a in dp.all_buckets()
        for name in ("_step_sparse_j", "_step_many_sparse_j")]
    assert (cold_dev["programs_loaded"], cold_dev["programs_built"]) == (0, n)
    # Second boot: the round programs are loaded - no compile line of
    # theirs - and _read_many is still compiled, by jit, after them.
    assert warm == ["Compiling jit(_read_many)"]
    assert [o[0] for o in warm_ours] == ["loaded"] * n
    assert [o[1] for o in warm_ours] == [o[1] for o in cold_ours]
    assert (warm_dev["programs_loaded"], warm_dev["programs_built"]) == (n, 0)
    assert warm_m["engine.programs_loaded"] == n
    assert cold_m["engine.programs_built"] == n
    assert json.dumps(warm_dev)  # wire-encodable, as admin.stats needs
