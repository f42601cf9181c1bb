"""chip_smoke.py — the quickest proof that the served path still starts on the chip.

Drives produce -> quorum round -> replicate -> persist -> ack -> consume
once, through the entry points a user would call, at the size of the
deployment the benchmark cells use (`benchmarks/configs/omb-1024p-100b.json`,
read, never copied: 3 brokers, topic `bench` with 1024 partitions at RF 3,
engine P=1024 R=3 slots=4608 slot_bytes=128 max_batch=512, full-copy
replication to 2 standbys, a durable --data-dir on every broker):

- three broker processes, `python -m ripplemq_tpu.broker --id N --config F
  --data-dir D`; load from `ProducerClient` / `ConsumerClient` over real
  TCP in separate client processes;
- stream, made from --seed: 1,048,576 distinct 100-byte messages in
  `produce_batch`es of 512 over all 1024 partitions, then 16,384 more to
  one partition so its 4608-slot ring wraps three times and trim /
  retention engage (and, at the default segment size, every broker seals
  segments, so erasure runs in all three processes);
- checked, not assumed: every produce acked; every message consumed back
  count- and byte-exact per partition (the wrapped partition from offset
  0, below trim, through the store path); after a clean stop EACH of the
  three data dirs, scanned with `storage.segment.scan_store`, holds the
  whole acked stream; every broker's `admin.stats` is clean (no boot,
  duty, erasure or step errors, native store writer, controller still
  broker 0 at its boot epoch) and the controller reports a TPU, the
  Pallas append backend and its peak device memory;
- after the brokers have exited (chip released), one more child compiles
  and runs the RS kernel on the chip at the stripe shard classes and one
  segment-scale shard, byte-compared to the numpy reference.

One process per chip: this parent never initialises a JAX backend. The
controller (broker 0) and, later, the kernel child inherit the
environment — the smoke never sets JAX_PLATFORMS for a process that must
find the chip — while standbys, clients and store scanners run with
JAX_PLATFORMS=cpu.

Exit codes: 0 = everything passed on an accelerator at the full shape;
1 = a functional check failed; 4 = every functional check passed but the
device check refused (no TPU, or --tiny). No flag lets a CPU run pass.
Only a passing run prints a result: its last stdout line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
A refused run's summary goes to stderr. Times printed along the way are
set-up information (cold vs cached compile), not speed claims.

    python chip_smoke.py                      # the chip, full shape
    python chip_smoke.py --engine-mode spmd   # four chips: 3 replicas on 3 devices
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # functional pass, exit 4
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TOPIC = "bench"
MSG_BYTES = 100
EXIT_FUNCTIONAL = 1
EXIT_DEVICE = 4
DEADLINE_S = 1100.0  # whole-run watchdog, inside the 1200 s contract
# The one definition of the deployment, shared with the benchmark cells.
DEPLOYMENT = os.path.join(REPO, "benchmarks", "configs", "omb-1024p-100b.json")


def deployment_raw(ports: list[int]) -> dict:
    """The benchmark deployment's cluster file with brokers on `ports`:
    the config's `cluster` block, its topics, and one broker per port —
    what benchmarks/run.py boots for the omb-1024p-100b cells."""
    with open(DEPLOYMENT) as f:
        config = json.load(f)
    raw = dict(config["cluster"], engine=dict(config["cluster"]["engine"]))
    raw["brokers"] = [{"id": i, "host": "127.0.0.1", "port": p}
                      for i, p in enumerate(ports)]
    raw["topics"] = config["deployment"]["topics"]
    return raw


def log(*a) -> None:
    print(*a, flush=True)


# ------------------------------------------------------------------ stream

def partition_messages(seed: int, p: int, n: int) -> list[bytes]:
    """The n messages of partition p, in produce order: 100 bytes each,
    an 8-byte (partition, index) head — distinctness by construction —
    then 92 bytes from a generator keyed on (seed, partition)."""
    import numpy as np

    body = np.empty((n, MSG_BYTES), np.uint8)
    head = np.empty((n, 2), "<u4")
    head[:, 0] = p
    head[:, 1] = np.arange(n)
    body[:, :8] = head.view(np.uint8).reshape(n, 8)
    body[:, 8:] = np.random.default_rng([seed, p]).integers(
        0, 256, (n, MSG_BYTES - 8), dtype=np.uint8)
    blob = body.tobytes()
    return [blob[i * MSG_BYTES:(i + 1) * MSG_BYTES] for i in range(n)]


def stream_plan(spec: dict) -> dict[int, int]:
    """partition -> message count: two batches everywhere, plus the wrap
    stream on one partition."""
    plan = {p: 2 * spec["batch"] for p in range(spec["partitions"])}
    plan[spec["wrap_partition"]] += spec["wrap_messages"]
    return plan


# --------------------------------------------------------- client children

def _in_threads(run, n: int) -> float:
    """run(tid) on n threads; seconds until the last one ends."""
    t0 = time.time()
    ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return time.time() - t0


def _await_go() -> None:
    """Children boot while the cluster does (imports off the clock),
    then wait for the parent's GO."""
    log("READY")
    if sys.stdin.readline().strip() != "GO":
        raise SystemExit("parent went away before GO")


def role_produce(spec: dict) -> dict:
    from ripplemq_tpu.client import ProducerClient

    threads, batch, seed = spec["threads"], spec["batch"], spec["seed"]
    mine = list(range(spec["proc_id"], spec["partitions"], spec["nprocs"]))
    plan = stream_plan(spec)
    errors: list[str] = []
    acked = [0] * threads
    first_ack = [None] * threads
    _await_go()  # the cluster is up: the client's first metadata fetch lands
    pc = ProducerClient(spec["bootstrap"], rpc_timeout_s=120.0)

    def run(tid: int) -> None:
        try:
            parts = mine[tid::threads]
            msgs = {p: partition_messages(seed, p, plan[p]) for p in parts}
            # (a) two passes of one 512-batch per partition; then (b)
            # the wrap stream, in order, on whoever owns that partition.
            chunks = [(p, i) for i in (0, 1) for p in parts]
            if spec["wrap_partition"] in parts:
                chunks += [(spec["wrap_partition"], i)
                           for i in range(2, plan[spec["wrap_partition"]]
                                          // batch)]
            for p, i in chunks:
                pc.produce_batch(TOPIC, msgs[p][i * batch:(i + 1) * batch],
                                 partition=p)
                if first_ack[tid] is None:
                    first_ack[tid] = time.time()
                acked[tid] += batch
        except Exception as e:  # a dead producer FAILS the smoke
            errors.append(f"producer thread {tid}: {type(e).__name__}: {e}")

    secs = _in_threads(run, threads)
    pc.close()
    return {"acked": sum(acked), "errors": errors, "secs": secs,
            "first_ack_wall": min((t for t in first_ack if t), default=None)}


def role_consume(spec: dict) -> dict:
    from ripplemq_tpu.client import ConsumerClient

    threads, seed = spec["threads"], spec["seed"]
    mine = list(range(spec["proc_id"], spec["partitions"], spec["nprocs"]))
    plan = stream_plan(spec)
    errors: list[str] = []
    counts = [0] * threads
    _await_go()

    def run(tid: int) -> None:
        cc = ConsumerClient(
            spec["bootstrap"],
            f"smoke-{spec['seed']}-{spec['proc_id']}-{tid}",
            max_messages=spec["read_batch"], rpc_timeout_s=60.0,
        )
        try:
            for p in mine[tid::threads]:
                want = partition_messages(seed, p, plan[p])
                got: list[bytes] = []
                idle = 0
                # A fresh consumer id starts at offset 0: on the wrapped
                # partition that is far below trim — the store path.
                while len(got) < len(want) and idle < 100:
                    msgs = cc.consume(TOPIC, partition=p)
                    if msgs:
                        got.extend(msgs)
                        idle = 0
                    else:
                        idle += 1
                        time.sleep(0.1)
                got.extend(cc.consume(TOPIC, partition=p))  # nothing extra
                counts[tid] += len(got)
                if got != want:
                    bad = next((i for i, (a, b) in enumerate(zip(got, want))
                                if a != b), min(len(got), len(want)))
                    errors.append(
                        f"partition {p}: read {len(got)} of {len(want)} "
                        f"messages, first difference at message {bad}")
        except Exception as e:
            errors.append(f"consumer thread {tid}: {type(e).__name__}: {e}")
        finally:
            cc.close()

    secs = _in_threads(run, threads)
    return {"consumed": sum(counts), "errors": errors, "secs": secs}


def role_scan(spec: dict) -> dict:
    """One data dir, read the way recovery reads it: every acked message
    of every partition must be there, byte-exact and in order."""
    import numpy as np

    from ripplemq_tpu.storage.segment import REC_APPEND, scan_store

    SB = spec["slot_bytes"]
    rows: dict[int, dict[int, bytes]] = {}
    for rec_type, slot, base, payload in scan_store(spec["store_dir"]):
        if rec_type == REC_APPEND:
            rows.setdefault(slot, {})[base] = payload
    plan = stream_plan(spec)
    errors: list[str] = []
    total = 0
    seen_parts = set()
    for slot, recs in rows.items():
        msgs: list[bytes] = []
        for base in sorted(recs):
            block = np.frombuffer(recs[base], np.uint8).reshape(-1, SB)
            lens = block[:, :4].copy().view("<i4")[:, 0]
            blob = block[:, 8:].tobytes()
            w = SB - 8
            msgs.extend(blob[i * w:i * w + int(n)]
                        for i, n in enumerate(lens) if n > 0)
        if not msgs:
            continue
        p = struct.unpack_from("<I", msgs[0])[0]
        seen_parts.add(p)
        total += len(msgs)
        want = partition_messages(spec["seed"], p, plan.get(p, 0))
        if msgs != want:
            errors.append(f"slot {slot} (partition {p}): holds {len(msgs)} "
                          f"messages, acked {len(want)}")
    missing = sorted(set(plan) - seen_parts)
    if missing:
        errors.append(f"{len(missing)} partitions absent, first {missing[:4]}")
    seg_dir = spec["store_dir"]
    sealed = sorted(f for f in os.listdir(seg_dir)
                    if f.startswith("segment-") and f.endswith(".log"))[:-1]
    sealed = [f for f in sealed if os.path.getsize(os.path.join(seg_dir, f))]
    rs_dir = os.path.join(seg_dir, "rs")
    shards = os.listdir(rs_dir) if os.path.isdir(rs_dir) else []
    return {"messages": total, "errors": errors[:8],
            "sealed_segments": len(sealed),
            "shard_files": len([f for f in shards if ".shard" in f
                                and not f.endswith(".tmp")])}


def role_kernel(spec: dict) -> dict:
    """The RS kernel on whatever this process's default backend is —
    compiled by Mosaic on a TPU, interpreted anywhere else."""
    from ripplemq_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import numpy as np

    from ripplemq_tpu.ops.rs import _gf_matmul_jit, gf_matmul, gf_matmul_ref
    from ripplemq_tpu.ops.rs import generator_matrix
    from ripplemq_tpu.stripes.codec import RS_K, RS_M, _shard_class

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    classes, n = [], 1
    while n <= spec["stripe_class_max"]:
        classes.append(_shard_class(n))
        n = classes[-1] + 1
    if not on_tpu:  # the interpreter is slow: both ends and the middle
        classes = sorted({classes[0], classes[len(classes) // 2], classes[-1]})
    sizes = classes + [-(-spec["segment_bytes"] // RS_K)]
    G = generator_matrix(RS_K, RS_M)
    rng = np.random.default_rng(spec["seed"])
    bad = []
    for n in sizes:
        x = rng.integers(0, 256, (RS_K, n), dtype=np.uint8)
        got = np.asarray(gf_matmul(G, x, use_pallas=on_tpu,
                                   interpret=not on_tpu))
        if not np.array_equal(got, gf_matmul_ref(G, x)):
            bad.append(n)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "sizes": len(sizes), "max_bytes": max(sizes),
            "programs": _gf_matmul_jit._cache_size(),
            "mismatched": bad, "mode": "mosaic" if on_tpu else "interpret"}


ROLES = {"produce": role_produce, "consume": role_consume,
         "scan": role_scan, "kernel": role_kernel}


# ------------------------------------------------------------------ parent

class Smoke:
    def __init__(self, args) -> None:
        self.args = args
        self.t_start = time.time()
        self.work = tempfile.mkdtemp(prefix="chip-smoke-")
        self.children: list[tuple[str, subprocess.Popen]] = []
        self.failures: list[str] = []
        # Children that own no chip. The controller and the kernel child
        # get the environment exactly as it came (env=None).
        self.cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")

    # -- processes ---------------------------------------------------------
    def spawn(self, name: str, argv: list[str], env: dict | None,
              pipe: bool = False) -> subprocess.Popen:
        err = open(os.path.join(self.work, f"{name}.stderr"), "wb")
        out = subprocess.PIPE if pipe else open(
            os.path.join(self.work, f"{name}.stdout"), "wb")
        proc = subprocess.Popen(
            [sys.executable] + argv, cwd=REPO, env=env, stderr=err,
            stdout=out, stdin=subprocess.PIPE if pipe else subprocess.DEVNULL,
            text=pipe, start_new_session=True,
        )
        self.children.append((name, proc))
        return proc

    def tail(self, name: str, n: int = 2500) -> str:
        out = []
        for ext in ("stdout", "stderr"):
            path = os.path.join(self.work, f"{name}.{ext}")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    text = f.read()[-n:].decode("utf-8", "replace").strip()
                if text:
                    out.append(f"--- {name}.{ext} (tail) ---\n{text}")
        return "\n".join(out)

    def stop_all(self) -> None:
        for _, proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        for _, proc in self.children:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def remaining(self) -> float:
        return DEADLINE_S - (time.time() - self.t_start)

    def fail(self, msg: str) -> None:
        log(f"FAIL: {msg}")
        self.failures.append(msg)

    # -- a role child: READY -> GO -> one RESULT line ---------------------
    def start_role(self, role: str, name: str, spec: dict,
                   env: dict | None):
        path = os.path.join(self.work, f"{name}.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        return name, self.spawn(
            name, [os.path.abspath(__file__), "--role", role, "--spec", path],
            env, pipe=True)

    def go(self, kids) -> None:
        for name, proc in kids:
            line = proc.stdout.readline().strip()
            if line != "READY":
                raise RuntimeError(f"{name} answered {line!r}, not READY\n"
                                   + self.tail(name))
        for _, proc in kids:
            proc.stdin.write("GO\n")
            proc.stdin.flush()

    def results(self, kids, what: str) -> list[dict]:
        out = []
        for name, proc in kids:
            line = ""
            for line in proc.stdout:  # last line wins; EOF ends the wait
                if line.startswith("RESULT "):
                    break
            proc.wait(timeout=max(5.0, self.remaining()))
            if not line.startswith("RESULT ") or proc.returncode != 0:
                raise RuntimeError(
                    f"{what} child {name} ended rc={proc.returncode} "
                    f"without a result\n" + self.tail(name))
            out.append(json.loads(line[len("RESULT "):]))
        return out

    # -- the run -------------------------------------------------------------
    def run(self) -> int:
        watchdog = threading.Timer(DEADLINE_S, self._timed_out)
        watchdog.daemon = True
        watchdog.start()
        try:
            summary = self._run()
        except Exception as e:
            self.fail(f"{type(e).__name__}: {e}")
            summary = {}
        finally:
            watchdog.cancel()
            self.stop_all()
        functional_ok = not self.failures
        device_ok = bool(summary.get("device_ok"))
        ok = functional_ok and device_ok and not self.args.tiny
        summary.update(ok=ok, functional_ok=functional_ok,
                       device_ok=device_ok, failures=self.failures[:10],
                       seconds=round(time.time() - self.t_start, 1))
        if ok:
            shutil.rmtree(self.work, ignore_errors=True)
            log(json.dumps({"ok": True, "device": summary["device"]}))
            return 0
        if functional_ok:
            shutil.rmtree(self.work, ignore_errors=True)
        else:
            self._print_tails()
            print(f"work dir kept: {self.work}", file=sys.stderr)
        # A refused run prints no result on stdout; its summary is here.
        print(json.dumps(summary), file=sys.stderr, flush=True)
        return EXIT_DEVICE if functional_ok else EXIT_FUNCTIONAL

    def _print_tails(self) -> None:
        for name, _ in self.children:
            t = self.tail(name)
            if t:
                print(t, file=sys.stderr)

    def _timed_out(self) -> None:
        print(f"FAIL: watchdog — no end after {DEADLINE_S:.0f}s",
              file=sys.stderr, flush=True)
        self._print_tails()
        self.stop_all()
        os._exit(EXIT_FUNCTIONAL)

    def _run(self) -> dict:
        args = self.args
        import yaml

        from ripplemq_tpu.utils.compile_cache import (
            CHECKOUT_CACHE_DIR,
            ENV_VAR,
        )
        from ripplemq_tpu.wire.transport import RpcError, TcpClient

        # Built from what git would commit: the native store writer is
        # compiled from native/segstore.cpp by the brokers themselves.
        so = os.path.join(REPO, "native", "libsegstore.so")
        if os.path.exists(so):
            os.remove(so)

        socks = [socket.socket() for _ in range(3)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        raw = deployment_raw(ports)
        if args.tiny:
            # Functional pass only (CPU, seconds): same topology and code
            # paths, a ring that still wraps three times, segments small
            # enough to seal. Never reported as ok.
            raw["engine"].update(partitions=8, slots=256, max_batch=32,
                                 read_batch=64)
            raw["topics"] = [dict(t, partitions=8) for t in raw["topics"]]
            raw.update(segment_bytes=32 << 10, metadata_election_timeout_s=1.5)
        eng = raw["engine"]
        cfg_path = os.path.join(self.work, "cluster.yaml")
        with open(cfg_path, "w") as f:
            yaml.safe_dump(raw, f)
        bootstrap = [f"127.0.0.1:{p}" for p in ports]
        spec = {
            "bootstrap": bootstrap, "seed": args.seed,
            "partitions": eng["partitions"], "batch": eng["max_batch"],
            "read_batch": eng["read_batch"], "slot_bytes": eng["slot_bytes"],
            "wrap_partition": args.seed % eng["partitions"],
            # three laps of the ring (256 / 4608 slots) and then some,
            # in whole batches
            "wrap_messages": 800 if args.tiny else 16384,
            "nprocs": 2 if args.tiny else 4,
            "threads": 2 if args.tiny else 8,
            "segment_bytes": raw["segment_bytes"],
            "stripe_class_max": (64 << 10) if args.tiny else (4 << 20),
        }
        total = sum(stream_plan(spec).values())
        def entries(d: str) -> int:
            return len(os.listdir(d)) if os.path.isdir(d) else 0

        cache_dir = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
        cache_before = entries(cache_dir)
        checkout_before = entries(CHECKOUT_CACHE_DIR)
        log(f"deployment: 3 brokers, {eng['partitions']} partitions RF 3, "
            f"engine {eng}, engine mode {args.engine_mode}")
        log(f"stream: seed {args.seed}, {total} messages of {MSG_BYTES} B "
            f"({spec['wrap_messages']} extra on partition "
            f"{spec['wrap_partition']}), compile cache {cache_dir} "
            f"({cache_before} entries)")

        # ---- brokers: the controller inherits the environment; the
        # standbys own no chip.
        t_spawn = time.time()
        brokers = []
        for i in range(3):
            argv = ["-m", "ripplemq_tpu.broker", "--id", str(i),
                    "--config", cfg_path, "--data-dir", self.work,
                    "--log-level", "WARNING"]
            if i == 0:
                argv += ["--engine-mode", args.engine_mode]
            brokers.append(self.spawn(
                f"broker-{i}", argv, None if i == 0 else self.cpu_env))
        client_spec = lambda i: dict(spec, proc_id=i)  # noqa: E731
        producers = [
            self.start_role("produce", f"produce-{i}", client_spec(i),
                            self.cpu_env)
            for i in range(spec["nprocs"])
        ]

        rpc = TcpClient()

        def stats(i: int, **kw) -> dict:
            return rpc.call(bootstrap[i], {"type": "admin.stats", **kw},
                            timeout=30.0)

        def alive_or_raise() -> None:
            for i, b in enumerate(brokers):
                if b.poll() is not None:
                    raise RuntimeError(
                        f"broker {i} exited rc={b.returncode} mid-run\n"
                        + self.tail(f"broker-{i}"))

        # ---- ready = engine up on broker 0, every partition led, both
        # standbys in the replicated set (three copies from the first ack).
        boot = None
        while True:
            alive_or_raise()
            if self.remaining() < 60:
                raise RuntimeError("cluster never became ready\n"
                                   + self.tail("broker-0"))
            try:
                st = stats(0)
            except (RpcError, OSError):
                time.sleep(0.5)
                continue
            parts = st["topics"].get(TOPIC, {})
            if (st["engine"] is not None and st["controller"]["is_self"]
                    and len(parts) == eng["partitions"]
                    and all(a["leader"] is not None for a in parts.values())
                    and sorted(st["controller"]["standbys"]) == [1, 2]):
                boot = st
                break
            time.sleep(0.5)
        boot_epoch = boot["controller"]["epoch"]
        log(f"cluster ready after {time.time() - t_spawn:.1f}s "
            f"(controller epoch {boot_epoch}); engine device: "
            f"{json.dumps(boot['engine']['device'])}")

        # ---- produce
        self.go(producers)
        res = self._wait(producers, "produce", alive_or_raise)
        acked = sum(r["acked"] for r in res)
        for r in res:
            for e in r["errors"]:
                self.fail(f"produce: {e}")
        first_ack_s = min(r["first_ack_wall"] for r in res
                          if r["first_ack_wall"]) - t_spawn
        log(f"time to first ack (boot + cold or cached compile): "
            f"{first_ack_s:.1f}s; produce phase "
            f"{max(r['secs'] for r in res):.1f}s")
        if acked != total:
            self.fail(f"acked {acked} of {total} messages")
        else:
            log(f"produce: {acked} messages acked ok")

        # ---- consume (fresh consumer ids: everything from offset 0)
        consumers = [
            self.start_role("consume", f"consume-{i}", client_spec(i),
                            self.cpu_env)
            for i in range(spec["nprocs"])
        ]
        self.go(consumers)
        res = self._wait(consumers, "consume", alive_or_raise)
        consumed = sum(r["consumed"] for r in res)
        for r in res:
            for e in r["errors"]:
                self.fail(f"consume: {e}")
        if consumed != total:
            self.fail(f"consumed {consumed} of {total} messages")
        else:
            log(f"consume: {consumed} messages, count- and byte-exact per "
                f"partition ({max(r['secs'] for r in res):.1f}s)")

        # ---- every broker's admin.stats
        device = None
        device_ok = False
        for i in range(3):
            st = stats(i, slots=[spec["wrap_partition"]]) if i == 0 \
                else stats(i)
            for key, want in (("boot_failures", 0), ("duty_errors", []),
                              ("erasure_errors", []), ("store_native", True),
                              ("store_quarantined", False)):
                if st[key] != want:
                    self.fail(f"broker {i}: {key} = {st[key]!r}")
            ctl = st["controller"]
            if ctl["id"] != 0 or ctl["epoch"] != boot_epoch:
                self.fail(f"broker {i}: controller moved to {ctl['id']} "
                          f"epoch {ctl['epoch']} (booted 0/{boot_epoch})")
            if i != 0:
                if st["engine"] is not None:
                    self.fail(f"broker {i} runs an engine")
                continue
            e = st["engine"]
            if e is None:
                self.fail("broker 0 lost its engine")
                continue
            device = e["device"]
            log(f"controller engine: mode {e['mode']}, rounds {e['rounds']}, "
                f"dispatches {e['dispatches']}, committed_entries "
                f"{e['committed_entries']}, device {json.dumps(device)}")
            if e["mode"] != args.engine_mode:
                self.fail(f"engine mode {e['mode']}")
            if e["step_errors"] != 0:
                self.fail(f"engine.step_errors = {e['step_errors']}")
            if e["committed_entries"] < total:
                self.fail(f"committed_entries {e['committed_entries']} "
                          f"< acked {total}")
            w = e["slots"][str(spec["wrap_partition"])]
            log(f"wrapped partition {spec['wrap_partition']}: {w} "
                f"(ring {eng['slots']} slots)")
            if w["log_end"] < 3 * eng["slots"] or w["trim"] <= 0:
                self.fail(f"ring never wrapped under trim: {w}")
            ring = (eng["replicas"] * eng["partitions"]
                    * (eng["slots"] + eng["max_batch"]) * eng["slot_bytes"])
            if device["peak_bytes_in_use"] is not None:
                log(f"peak device bytes {device['peak_bytes_in_use']} "
                    f"vs ring {ring} "
                    f"(x{device['peak_bytes_in_use'] / ring:.2f})")
            held = [tuple(d) for d in device["replica_devices"]]
            if args.engine_mode == "spmd":
                log(f"mesh {device['mesh']}, replica -> devices {held}")
                if len(set(held)) != eng["replicas"] or any(
                        len(h) != 1 for h in held):
                    self.fail(f"replicas not on {eng['replicas']} distinct "
                              f"devices: {held}")
            device_ok = (device["platform"] == "tpu"
                         and bool(device["device_kind"])
                         and device["device_count"] >= 1
                         and device["append_backend"] == "pallas"
                         and device["peak_bytes_in_use"] is not None)
            if not device_ok:
                log(f"device check REFUSED: {json.dumps(device)}")
        rpc.close()

        # ---- clean stop: SIGTERM, exit 0, chip released
        for b in brokers:
            b.send_signal(signal.SIGTERM)
        for i, b in enumerate(brokers):
            try:
                rc = b.wait(timeout=max(5.0, min(180.0, self.remaining())))
            except subprocess.TimeoutExpired:
                rc = None
            if rc != 0:
                self.fail(f"broker {i} did not stop cleanly (rc={rc})\n"
                          + self.tail(f"broker-{i}"))

        # ---- three copies on disk: each data dir holds the whole stream
        scans = [
            self.start_role(
                "scan", f"scan-{i}",
                dict(spec, store_dir=os.path.join(
                    self.work, f"broker-{i}", "segments")),
                self.cpu_env)
            for i in range(3)
        ]
        self.go(scans)
        for i, r in enumerate(self._wait(scans, "scan")):
            for e in r["errors"]:
                self.fail(f"data dir {i}: {e}")
            if r["messages"] != total:
                self.fail(f"data dir {i} holds {r['messages']} of {total}")
            if r["sealed_segments"] < 1:
                self.fail(f"data dir {i} sealed no segment")
            if r["shard_files"] != 5 * r["sealed_segments"]:
                self.fail(f"data dir {i}: {r['shard_files']} RS shards for "
                          f"{r['sealed_segments']} sealed segments")
            log(f"data dir {i}: {r['messages']} messages byte-exact, "
                f"{r['sealed_segments']} sealed segments, "
                f"{r['shard_files']} RS shards")

        # ---- the RS kernel, alone on the released chip
        kern = [self.start_role("kernel", "kernel", spec, None)]
        self.go(kern)
        k = self._wait(kern, "kernel")[0]
        log(f"rs kernel ({k['mode']}): {k['sizes']} shard sizes up to "
            f"{k['max_bytes']} B in {k['programs']} programs (one per "
            f"shard-length bucket) on {k['platform']}/{k['kind']}, "
            f"mismatched {k['mismatched']}")
        if k["mismatched"]:
            self.fail(f"rs kernel differs from the reference at "
                      f"{k['mismatched']}")
        kdev = {"platform": k["platform"], "kind": k["kind"],
                "count": k["count"]}
        if device is not None:
            sdev = {"platform": device["platform"],
                    "kind": device["device_kind"],
                    "count": device["device_count"]}
            if sdev != kdev:
                self.fail(f"controller ran on {sdev}, kernel child on {kdev}")
        device_ok = device_ok and k["mode"] == "mosaic"

        # ---- compile cache: where it was placed, and only there
        cache_after = entries(cache_dir)
        log(f"compile cache {cache_dir}: {cache_before} -> {cache_after} "
            f"entries")
        if device_ok and cache_after == 0:
            # (a CPU run's sub-second compiles fall under JAX's caching
            # threshold; on the chip every engine program clears it)
            self.fail(f"compile cache {cache_dir} is empty after the run")
        if (cache_dir != CHECKOUT_CACHE_DIR
                and entries(CHECKOUT_CACHE_DIR) != checkout_before):
            self.fail(f"{ENV_VAR} is set yet {CHECKOUT_CACHE_DIR} was "
                      f"written")

        import importlib.metadata as md

        versions = {}
        for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
            try:
                versions[pkg] = md.version(pkg)
            except md.PackageNotFoundError:
                versions[pkg] = None
        log(f"versions: python {sys.version.split()[0]}, {versions}")
        return {
            "device": kdev, "device_ok": device_ok,
            "engine_device": device, "engine_mode": args.engine_mode,
            "seed": args.seed, "messages": total, "tiny": args.tiny,
            "first_ack_s": round(first_ack_s, 1),
            "cache": {"dir": cache_dir, "before": cache_before,
                      "after": cache_after},
            "versions": versions,
        }

    def _wait(self, kids, what: str, check=None) -> list[dict]:
        """Collect role results while watching the brokers: a broker that
        dies mid-phase must fail the run at once, not hang the clients."""
        done = threading.Event()

        def watch() -> None:
            while not done.wait(1.0):
                try:
                    check()
                except RuntimeError as e:
                    self.fail(str(e))
                    for _, proc in kids:
                        proc.kill()
                    return

        if check is not None:
            threading.Thread(target=watch, daemon=True).start()
        try:
            return self.results(kids, what)
        finally:
            done.set()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the message stream")
    ap.add_argument("--engine-mode", default="local",
                    choices=["local", "spmd"],
                    help="controller engine binding (spmd: one replica per "
                         "device — the four-chip run)")
    ap.add_argument("--tiny", action="store_true",
                    help="functional pass at a toy shape (seconds on CPU); "
                         "never reported as ok")
    ap.add_argument("--role", choices=sorted(ROLES), help=argparse.SUPPRESS)
    ap.add_argument("--spec", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.role:
        with open(args.spec) as f:
            spec = json.load(f)
        if args.role in ("scan", "kernel"):
            _await_go()
        print("RESULT " + json.dumps(ROLES[args.role](spec)), flush=True)
        return 0
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
