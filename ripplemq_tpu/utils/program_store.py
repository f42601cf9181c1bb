"""A store of finished engine programs: a boot loads what the last built.

JAX's persistent cache (utils/compile_cache.py) keeps the backend
compile only, a tenth of a controller's boot. What it cannot keep is
the Python in front of it: tracing a round program (~2 s) and lowering
its Pallas kernel to Mosaic (1-3.6 s), a dozen programs a boot, every
boot, with the device lock held - for programs whose inputs (source,
shapes, compiler) have not changed since the last boot. So the first
process that builds a program (trace -> lower -> compile, exactly as
`jit` does) also writes the finished executable here, and every later
process with the same key loads it: no trace, no lowering, no compile.
The loaded program IS the built one - the same XLA module and op names,
the same donation, called through the same C++ path as a jitted
function.

The key is everything the program was made from: the bytes of every
source file of the package, every `EngineConfig` value, the function,
the call's argument tree with its shapes and dtypes, the `jax`/`jaxlib`
versions, the backend's platform version (libtpu's build on a TPU), the
device kind and count, and the write phase compiled in. Over-keying
costs one rebuild; under-keying would run a stale program. An artifact
that is missing, truncated, unreadable or of another key is a miss:
logged, rebuilt, rewritten (temp + rename - the brokers of one cluster
may start at once). Nothing here raises into a caller: whatever fails,
the call goes through `jit` as it always did.

Where it lives follows the compile cache's one rule (`cache_dir()`): a
`programs/` directory under it, and none for a process pinned to the
CPU backend, whose programs build in milliseconds. Without a directory
`wrap` hands the jitted function back untouched.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import tempfile
import time
from typing import Any, Callable, Optional

import jax
import jaxlib
from jax.experimental.serialize_executable import (
    deserialize_and_load,
    serialize,
)
from jax.tree_util import tree_flatten, tree_leaves, tree_structure

from ripplemq_tpu.utils.compile_cache import cache_dir
from ripplemq_tpu.utils.logs import get_logger

log = get_logger("programs")

_MAGIC = b"ripplemq-program-1\n"
_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_directory() -> Optional[str]:
    """`programs/` under the compile cache's directory; None where the
    rule leaves the process alone (pinned to the CPU backend)."""
    base = cache_dir()
    return None if base is None else os.path.join(base, "programs")


def source_digest(root: str = _PACKAGE_DIR) -> str:
    """sha256 over the path and bytes of every .py file under `root`, in
    sorted order. The whole package and not only core/, ops/ and
    parallel/ with what they import: no import graph to keep true, and
    a change elsewhere costs one rebuild."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def environment() -> dict:
    """What a program depends on besides its function, config and call:
    the source, the compiler stack and the device it is built for."""
    dev = jax.devices()[0]
    return {
        "source": source_digest(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "platform_version": dev.client.platform_version,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def signature(args: tuple) -> Optional[tuple]:
    """What tells one program of a function from another: the argument
    tree with each leaf's shape and dtype. None where a leaf has none (a
    Python scalar): such a call is `jit`'s own business."""
    leaves, tree = tree_flatten(args)
    try:
        return tree, tuple(
            (x.shape, x.dtype, getattr(x, "weak_type", False))
            for x in leaves)
    except AttributeError:
        return None


def program_key(name: str, signature: str, cfg: Any, append_backend: str,
                env: dict) -> str:
    """The digest an artifact is filed and checked under."""
    h = hashlib.sha256()
    for part in (name, signature, repr(cfg), append_backend,
                 repr(sorted(env.items()))):
        h.update(part.encode() + b"\0")
    return h.hexdigest()


def write_artifact(path: str, key: str, body: dict) -> None:
    """`body` under `key` at `path`, whole or not at all: a temp file in
    the same directory, then one rename."""
    blob = pickle.dumps(dict(body, key=key), protocol=4)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_MAGIC + hashlib.sha256(blob).digest() + blob)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def read_artifact(path: str, key: str) -> Optional[dict]:
    """The body filed at `path`, or None with the reason logged where
    the file is missing (silently: the ordinary first boot), cut short,
    not an artifact, or one of another key."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        return None
    except OSError as e:
        log.warning("program store: %s unreadable (%s): rebuilding", path, e)
        return None
    head = len(_MAGIC) + 32
    blob = raw[head:]
    if (not raw.startswith(_MAGIC)
            or hashlib.sha256(blob).digest() != raw[len(_MAGIC):head]):
        log.warning("program store: %s is cut short or no artifact: "
                    "rebuilding", path)
        return None
    try:
        body = pickle.loads(blob)
    except Exception as e:
        log.warning("program store: %s does not unpickle (%s: %s): "
                    "rebuilding", path, type(e).__name__, e)
        return None
    if not isinstance(body, dict) or body.get("key") != key:
        log.warning("program store: %s holds another key: rebuilding", path)
        return None
    return body


class ProgramStore:
    """One directory of artifacts and the count of what a process took
    from it (`loaded`) and put into it (`built`). `metrics`, where
    given, carries the same two counts as `engine.programs_loaded` and
    `engine.programs_built`."""

    def __init__(self, directory: Optional[str], metrics=None) -> None:
        self.directory = directory
        self.loaded = 0
        self.built = 0
        self._env: Optional[dict] = None  # environment(), read once
        self._c_loaded = self._c_built = None
        if metrics is not None:
            self._c_loaded = metrics.counter("engine.programs_loaded")
            self._c_built = metrics.counter("engine.programs_built")

    def wrap(self, jitted: Callable, cfg: Any, append_backend: str,
             describe: Optional[Callable[..., str]] = None) -> Callable:
        """`jitted` behind the store; `jitted` itself where there is no
        directory. `describe(*args)` words the boot line ("bucket 8")."""
        if self.directory is None:
            return jitted
        return StoredProgram(self, jitted, cfg, append_backend, describe)

    def environment(self) -> dict:
        if self._env is None:
            self._env = environment()
        return self._env

    def _count(self, loaded: bool) -> None:
        if loaded:
            self.loaded += 1
        else:
            self.built += 1
        c = self._c_loaded if loaded else self._c_built
        if c is not None:
            c.inc()


class StoredProgram:
    """A jitted function whose programs come from the store: one
    executable per (argument tree, shapes, dtypes), loaded where an
    artifact of the key exists, built as `jit` builds it and written
    where none does."""

    def __init__(self, store: ProgramStore, jitted: Callable, cfg: Any,
                 append_backend: str,
                 describe: Optional[Callable[..., str]]) -> None:
        self._store = store
        self._jitted = jitted
        self._cfg = cfg
        self._append_backend = append_backend
        self._describe = describe
        self.name = jitted.__name__
        self._programs: dict = {}

    def __call__(self, *args):
        sig = signature(args)
        if sig is None:
            return self._jitted(*args)
        prog = self._programs.get(sig)
        if prog is None:
            prog = self._programs[sig] = self._resolve(sig, args)
        return prog(*args)

    def _resolve(self, sig: tuple, args: tuple) -> Callable:
        """The program for this call: loaded, else built and written,
        else - whatever went wrong - the jitted function."""
        store, name = self._store, self.name
        what = name
        if self._describe is not None:
            what = f"{name} {self._describe(*args)}"
        try:
            key = program_key(name, repr(sig), self._cfg,
                              self._append_backend, store.environment())
            path = os.path.join(store.directory, f"{name}-{key[:40]}.prog")
            t0 = time.perf_counter()
            prog = _load(path, key, args)
        except Exception as e:
            log.warning("program store: no key or load for %s (%s: %s): "
                        "the traced path", what, type(e).__name__, e)
            return self._jitted
        if prog is not None:
            store._count(loaded=True)
            log.warning("loaded %s in %.2f s", what,
                        time.perf_counter() - t0)
            return prog
        try:
            t0 = time.perf_counter()
            traced = self._jitted.trace(*args)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            compiled = lowered.compile()
            t3 = time.perf_counter()
        except Exception as e:
            # jit raises the same from the same arguments, or builds
            # what this could not: either way the call is jit's.
            log.warning("program store: %s not built ahead of its call "
                        "(%s: %s): the traced path", what,
                        type(e).__name__, e)
            return self._jitted
        store._count(loaded=False)
        try:
            _save(path, key, compiled)
        except Exception as e:
            log.warning("program store: %s built but not written (%s: %s)",
                        what, type(e).__name__, e)
        log.warning("built %s: trace %.2f s lower %.2f s compile %.2f s "
                    "write %.2f s", what, t1 - t0, t2 - t1, t3 - t2,
                    time.perf_counter() - t3)
        return compiled


def _save(path: str, key: str, compiled) -> None:
    payload, _, out_tree = serialize(compiled)
    devices = sorted({d.id for s in tree_leaves(compiled.input_shardings)
                      for d in s.device_set})
    write_artifact(path, key, {"payload": payload, "out_tree": out_tree,
                               "devices": devices})


def _load(path: str, key: str, args: tuple) -> Optional[Callable]:
    """The executable filed under `key`, loaded onto the devices it was
    built for; None (logged) where there is none to be had."""
    body = read_artifact(path, key)
    if body is None:
        return None
    try:
        by_id = {d.id: d for d in jax.devices()}
        return deserialize_and_load(
            body["payload"], tree_structure((args, {})), body["out_tree"],
            execution_devices=[by_id[i] for i in body["devices"]])
    except Exception as e:
        log.warning("program store: %s does not load (%s: %s): rebuilding",
                    path, type(e).__name__, e)
        return None
