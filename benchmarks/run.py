"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the cell's cluster (one process per broker; broker 0 owns the chip,
every other process is pinned to the CPU backend from outside), warms it
with the cell's own traffic, measures for --seconds, drains, stops the
brokers with SIGTERM, compares what was delivered and what every replica's
data dir holds with the plain reference, and prints the result as the last
line of stdout. Everything that belongs to one configuration, one traffic
mix or one per-layer metric is a file of its own, found by name:

    configs/<config>.json   workloads/<cell>.json   layer_metrics/<metric>.json
    generators/<kind>.py    readers/<kind>.py

This parent never imports JAX. No chip, an append backend other than
`pallas`, a broker error ring that is not empty, an election or an
unexpected compile inside the window: the run prints no result and exits
non-zero. `--rehearse` (the builder's dry run on JAX_PLATFORMS=cpu, at the
small sizes the files give under "rehearsal") skips the look for a chip,
prints its result to stderr only and exits 4.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

T_START_NS = time.monotonic_ns()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

EXIT_FAILED = 1
EXIT_NO_PROGRAM = 3
EXIT_REHEARSAL = 4
DEADLINE_S = 340.0       # a run ends inside the contract's 360 s ...
DEADLINE_COLD_S = 1150.0  # ... or 1200 s where it had to compile
PROFILE_S = 3.0
WARM_LAST = "jit(_read_many)"  # the last program DataPlane.warm builds
WARM_WAIT_S = 60.0
STAT_KEYS = (("boot_failures", 0), ("duty_errors", []), ("erasure_errors", []),
             ("store_native", True), ("store_quarantined", False))


def log(*a) -> None:
    print(*a, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """`over` laid on `base`, dict by dict."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


class RunFailed(Exception):
    pass


def metrics_for(cell: str, here: str = HERE) -> list[dict]:
    """The per-layer metrics a `--trace 1` run of `cell` reports, in the
    order of its line. The tie is written once, by whichever of the two
    came later, so that neither ever needs an edit of a file that is
    there: a cell's `workloads/<cell>.json` names under `layer_metrics`
    the definitions that were there before it (their files say what is
    read, not where); a metric that came after a cell names that cell
    under `workloads` in its own file. First the cell's list in its order,
    then the later files by name. A listed name with no file, a file under
    another name, a `moves` the cell does not report, a name listed twice
    or nothing to report at all is RunFailed, naming cell and metric."""
    def load(*parts: str) -> dict:
        with open(os.path.join(here, *parts)) as f:
            return json.load(f)

    spec = load("workloads", f"{cell}.json")
    names = list(spec.get("layer_metrics", []))
    files = {f[:-5]: load("layer_metrics", f) for f in sorted(os.listdir(
        os.path.join(here, "layer_metrics"))) if f.endswith(".json")}
    later = [name for name, m in files.items() if name not in names
             and cell in m.get("workloads", ())]
    if not names + later:
        raise RunFailed(f"cell {cell}: no per-layer metric: its file lists "
                        f"none and no metric's file names it")
    for name in names + later:
        m = files.get(name)
        if names.count(name) > 1:
            raise RunFailed(f"cell {cell}: lists per-layer metric {name} "
                            f"{names.count(name)} times")
        if m is None:
            raise RunFailed(f"cell {cell}: lists per-layer metric {name}, "
                            f"and layer_metrics/{name}.json is not there")
        if m["name"] != name:
            raise RunFailed(f"cell {cell}: layer_metrics/{name}.json holds "
                            f"the metric {m['name']}")
        if m["moves"] not in spec["end_to_end"]:
            raise RunFailed(f"cell {cell}: per-layer metric {name} moves "
                            f"{m['moves']}, which the cell does not report "
                            f"({', '.join(spec['end_to_end'])})")
    return [files[name] for name in names + later]


def failing(numbers: list) -> dict:
    """Of the numbers compared (name, value, limit), those outside their
    limit, by name. A limit is exact: `== N`, or 0."""
    return {name: int(value) for name, value, limit in numbers
            if value != (int(limit[3:]) if limit.startswith("==") else 0)}


def compared_lines(numbers: list) -> list[str]:
    bad = failing(numbers)
    return [f"compared {name} = {value} (limit {limit})"
            + ("  <-- FAILS" if name in bad else "")
            for name, value, limit in numbers]


def verdict(numbers: list, drain: dict) -> dict:
    """The end of a result line. Always, and last, `compared`: every
    number with its limit. Only where a number fails: that number again
    as a top-level scalar under its own name, with what the drain did
    (`drain`), because the driver's record of a refused run keeps the
    line's top-level scalars and little else."""
    bad = failing(numbers)
    out = dict(bad, **drain) if bad else {}
    out["compared"] = {name: [int(value), limit]
                       for name, value, limit in numbers}
    return out


def delivery_numbers(dl: dict, whole: bool, want_subs: int) -> list:
    """Comparison (a) as numbers beside their limits, from
    `reference_log.compare_subscriptions`: the counts summed over the
    cell's subscriptions, and how many subscriptions had a consumer
    report, held to the configuration's `deployment.subscriptions`."""
    return [("delivery.differ", dl["differ"], "0"),
            ("delivery.extra", dl["extra"], "0"),
            ("delivery.missing", dl["missing"],
             "0" if whole else "0 (prefix: lag allowed)"),
            ("delivery.subscriptions", dl["reported"], f"== {want_subs}")]


def warm_over(compile_log) -> bool:
    """Whether the launcher's compile log (lines of compiles.jsonl) shows
    WARM_LAST compiled or fetched from the cache."""
    seen = False
    for line in compile_log:
        try:
            msg = json.loads(line)["msg"]
        except ValueError:  # the line being written
            continue
        if msg.startswith("Compiling ") and WARM_LAST in msg:
            seen = True
        elif seen and msg.startswith(("Finished XLA compil",
                                      "Persistent compilation cache hit")):
            return True
    return False


class Run:
    """One run of one cell."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 rehearse: bool = False, cluster_overrides: dict | None = None,
                 fault: str | None = None,
                 t_start_ns: int = T_START_NS) -> None:
        self.t_start_ns = t_start_ns  # the process's start, unless a tool
        # such as control.py makes several runs in one process
        self.layer_metrics = metrics_for(workload)  # or RunFailed, at once
        self.cell = load_json("workloads", f"{workload}.json")
        self.config = load_json("configs", f"{self.cell['config']}.json")
        if rehearse:
            self.cell = merge(self.cell, self.cell.get("rehearsal", {}))
            self.config = merge(self.config, self.config.get("rehearsal", {}))
        if cluster_overrides:
            self.config["cluster"] = merge(self.config["cluster"],
                                           cluster_overrides)
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.rehearse = trace, rehearse
        self.faults = [f for f in (fault or "").split(",") if f]
        self.work = tempfile.mkdtemp(prefix="rmq-bench-")
        self.children: list[tuple[str, subprocess.Popen]] = []
        self.cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")
        dep = self.config["deployment"]
        self.size = int(dep["message_bytes"])
        self.streams = [(t["name"], p) for t in dep["topics"]
                        for p in range(t["partitions"])]
        self.n_brokers = int(dep["brokers"])
        # One form inside: a list. `subscription` is a list of one.
        self.subscriptions = list(self.cell["subscriptions"]) \
            if "subscriptions" in self.cell else [self.cell["subscription"]]
        self.numbers: list[tuple[str, float, str]] = []  # name, value, limit
        self.deadline_s = DEADLINE_S
        self.stopping = False
        self.brokers: list = []
        self._rpc = None

    # ------------------------------------------------------------ processes
    def spawn(self, name: str, argv: list[str], env: dict | None,
              pipe: bool = False) -> subprocess.Popen:
        err = open(os.path.join(self.work, f"{name}.stderr"), "wb")
        out = subprocess.PIPE if pipe else open(
            os.path.join(self.work, f"{name}.stdout"), "wb")
        proc = subprocess.Popen(
            [sys.executable] + argv, cwd=REPO, env=env, stderr=err, stdout=out,
            stdin=subprocess.PIPE if pipe else subprocess.DEVNULL,
            text=pipe, start_new_session=True)
        self.children.append((name, proc))
        return proc

    def tail(self, name: str, n: int = 2000) -> str:
        out = []
        for ext in ("stdout", "stderr"):
            path = os.path.join(self.work, f"{name}.{ext}")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    f.seek(max(0, os.path.getsize(path) - n))
                    text = f.read().decode("utf-8", "replace").strip()
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

    def start_role(self, role: str, name: str, spec: dict):
        path = os.path.join(self.work, f"{name}.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        return name, self.spawn(
            name, ["-m", "benchmarks.child", "--role", role, "--spec", path],
            self.cpu_env, pipe=True)

    def await_ready(self, kids) -> None:
        for name, proc in kids:
            line = proc.stdout.readline().strip()
            if line != "READY":
                raise RunFailed(f"{name} answered {line!r}, not READY\n"
                                + self.tail(name))

    @staticmethod
    def tell(kids, line: str) -> None:
        for _, proc in kids:
            proc.stdin.write(line + "\n")
            proc.stdin.flush()

    def results(self, kids, on_line=None) -> list:
        """Starts a reader thread per child (a child may print FIRSTACK
        long before its RESULT); `collect` takes the handle returned."""
        out: list = [None] * len(kids)

        def read(i: int, name: str, proc) -> None:
            for line in proc.stdout:
                if line.startswith("RESULT "):
                    out[i] = json.loads(line[len("RESULT "):])
                    break
                if on_line is not None:
                    on_line(line)
            proc.wait()

        ts = [threading.Thread(target=read, args=(i, n, p), daemon=True)
              for i, (n, p) in enumerate(kids)]
        for t in ts:
            t.start()
        return [ts, out]

    def collect(self, kids, handle, what: str) -> list[dict]:
        ts, out = handle
        for t, (name, proc) in zip(ts, kids):
            while t.is_alive():
                t.join(timeout=1.0)
                self.alive_or_raise()
                if self.remaining() < 5:
                    raise RunFailed(f"{what} child {name} did not end in time")
        for (name, proc), r in zip(kids, out):
            if r is None or proc.returncode != 0:
                raise RunFailed(f"{what} child {name} ended rc={proc.returncode}"
                                f" without a result\n" + self.tail(name))
        return out

    def remaining(self) -> float:
        return self.deadline_s - (time.monotonic_ns() - self.t_start_ns) / 1e9

    # --------------------------------------------------------------- brokers
    def stats(self, i: int) -> dict:
        return self._rpc.call(self.bootstrap[i], {"type": "admin.stats"},
                              timeout=30.0)

    @staticmethod
    def leaders(st: dict) -> dict:
        return {f"{t}/{p}": a["leader"] for t, parts in st["topics"].items()
                for p, a in parts.items()}

    @staticmethod
    def tally(leaders: dict) -> dict:
        out: dict = {}
        for b in leaders.values():
            out[b] = out.get(b, 0) + 1
        return dict(sorted(out.items()))

    def alive_or_raise(self) -> None:
        for i, b in enumerate(self.brokers):
            if b.poll() is not None and not self.stopping:
                raise RunFailed(f"broker {i} exited rc={b.returncode} mid-run\n"
                                + self.tail(f"broker-{i}"))

    def boot(self) -> None:
        """Ports, cluster file, broker processes."""
        from ripplemq_tpu.wire.transport import TcpClient

        socks = [socket.socket() for _ in range(self.n_brokers)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        raw = dict(self.config["cluster"])
        raw["brokers"] = [{"id": i, "host": "127.0.0.1", "port": p}
                          for i, p in enumerate(ports)]
        raw["topics"] = self.config["deployment"]["topics"]
        if self.trace:
            raw["trace_sample_n"] = int(self.cell.get("trace_sample_n", 8))
        cfg_path = os.path.join(self.work, "cluster.yaml")
        with open(cfg_path, "w") as f:
            json.dump(raw, f)  # JSON is YAML
        self.bootstrap = [f"127.0.0.1:{p}" for p in ports]
        cache = os.path.join(REPO, ".jax_cache")
        self.cold = not (os.path.isdir(cache) and os.listdir(cache)) \
            and not os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if self.cold:
            self.deadline_s = DEADLINE_COLD_S
        for i in range(self.n_brokers):
            argv = ["--id", str(i), "--config", cfg_path, "--data-dir",
                    self.work, "--log-level", "WARNING"]
            if i == 0:  # the chip's owner: environment as it came
                argv = ["-m", "benchmarks.launcher", self.work,
                        str(PROFILE_S if self.trace else 0), "--"] + argv
                env = None
            else:
                argv = ["-m", "ripplemq_tpu.broker"] + argv
                env = self.cpu_env
            self.brokers.append(self.spawn(f"broker-{i}", argv, env))
        self._rpc = TcpClient()

    def wait_ready(self) -> dict:
        """Engine up on broker 0, every partition led, the configured
        number of standbys in the replicated set."""
        from ripplemq_tpu.wire.transport import RpcError

        want_parts = {t["name"]: t["partitions"]
                      for t in self.config["deployment"]["topics"]}
        n_standby = int(self.config["cluster"].get("standby_count", 2))
        while True:
            self.alive_or_raise()
            if self.remaining() < 200:
                raise RunFailed("cluster never became ready\n"
                                + self.tail("broker-0"))
            try:
                st = self.stats(0)
            except (RpcError, OSError):
                time.sleep(0.25)
                continue
            led = all(
                len(st["topics"].get(t, {})) == n
                and all(a["leader"] is not None
                        for a in st["topics"][t].values())
                for t, n in want_parts.items())
            if (st["engine"] is not None and st["controller"]["is_self"]
                    and led
                    and len(st["controller"]["standbys"]) == n_standby):
                return st
            time.sleep(0.25)

    def await_warm(self) -> int:
        """The moment the broker's own warm-up is over, which as a rule is
        now: it builds its round programs with the device lock held and the
        probe is acked behind them. But `DataPlane.warm` lets go of the
        lock between two programs, and a probe that slips in there is
        acked with programs still to build, which then compile inside the
        window (PERF.md section 6, PR 31). The compile log says which it
        was: warm-up is over when its last program, WARM_LAST, has been
        compiled or fetched. Traffic starts only then."""
        path = os.path.join(self.work, "compiles.jsonl")
        until = time.monotonic() + WARM_WAIT_S
        while True:
            if os.path.exists(path):
                with open(path) as f:
                    if warm_over(f):
                        return time.monotonic_ns()
            self.alive_or_raise()
            if time.monotonic() > until:
                log(f"no {WARM_LAST} in the broker's compile log {WARM_WAIT_S}s"
                    f" after the first ack: going on without")
                return time.monotonic_ns()
            time.sleep(0.1)

    # ------------------------------------------------------------------ run
    def run(self) -> dict:
        watchdog = threading.Timer(DEADLINE_COLD_S, self._timed_out)
        watchdog.daemon = True
        watchdog.start()
        try:
            return self._run()
        except BaseException:
            for name, _ in self.children:
                t = self.tail(name, 1500)
                if t:
                    print(t, file=sys.stderr)
            raise
        finally:
            watchdog.cancel()
            self.stop_all()
            if self._rpc is not None:
                self._rpc.close()
            shutil.rmtree(self.work, ignore_errors=True)

    def _timed_out(self) -> None:
        print("FAIL: watchdog", file=sys.stderr, flush=True)
        self.stop_all()
        os._exit(EXIT_FAILED)

    def child_spec(self, side: str, i: int) -> dict:
        part = self.cell[side]
        spec = {
            "work": self.work, "seed": self.seed, "proc_id": i,
            "nprocs": int(part["processes"]), "streams": self.streams,
            "message_bytes": self.size, "bootstrap": self.bootstrap,
            "params": part["params"], "generator": part.get("generator"),
            "trace_sample_n": int(self.cell.get("trace_sample_n", 8))
            if self.trace else 0,
            "fault": next((f for f in self.faults if f.endswith("_delivered")),
                          None) if i == 0 else None,
        }
        if side == "consumers":
            from benchmarks.child import hosted

            subs = hosted(len(self.subscriptions), i, spec["nprocs"])
            if len(subs) > int(part["params"]["threads"]):
                raise RunFailed(
                    f"consumer process {i} hosts {len(subs)} subscriptions "
                    f"on {part['params']['threads']} threads: one would "
                    f"have no consumer")
            spec["subscriptions"] = [[self.subscriptions[q], q, k, of]
                                     for q, k, of in subs]
            # keyword arguments for the program's ConsumerClient beside
            # those the harness sets; the child holds them to its signature
            spec["client"] = dict(part.get("client", {}))
        return spec

    def _run(self) -> dict:
        import numpy as np

        from benchmarks import stats as bstats
        from benchmarks.child import load_records
        from benchmarks.reference_log import (ReferenceLog,
                                              compare_subscriptions)

        cell, cfg = self.cell, self.config
        log(f"cell {cell['name']}: seed {self.seed}, window {self.seconds}s, "
            f"trace {int(self.trace)}, config {cfg['name']} "
            f"({self.n_brokers} brokers, {len(self.streams)} partitions)")
        # ---- children boot while the cluster does
        self.boot()
        producers = [self.start_role("produce", f"produce-{i}",
                                     self.child_spec("producers", i))
                     for i in range(int(cell["producers"]["processes"]))]
        consumers = [self.start_role("consume", f"consume-{i}",
                                     self.child_spec("consumers", i))
                     for i in range(int(cell["consumers"]["processes"]))]
        boot = self.wait_ready()
        boot_epoch = boot["controller"]["epoch"]
        leaders_at_boot = self.leaders(boot)
        dev = boot["engine"]["device"]
        log(f"cluster ready after {(time.monotonic_ns() - self.t_start_ns) / 1e9:.1f}s"
            f" (controller epoch {boot_epoch}, standbys "
            f"{boot['controller']['standbys']}); device {json.dumps(dev)}")
        if not self.rehearse:
            if dev["platform"] != "tpu" or dev["append_backend"] != "pallas":
                raise RunFailed(f"no TPU with the Pallas append: {dev}")
            peaks = load_json("peaks.json")
            if dev["device_kind"] not in peaks:
                raise RunFailed(f"device kind {dev['device_kind']!r} is not "
                                f"in peaks.json")
            self.peaks = peaks[dev["device_kind"]]
        else:
            self.peaks = {"hbm_bytes_per_s": float("nan")}
        self.await_ready(producers + consumers)

        # ---- warm-up with the cell's own traffic, then the window
        first_ack = threading.Event()

        def on_line(line: str) -> None:
            if line.startswith("FIRSTACK"):
                first_ack.set()

        ph = self.results(producers, on_line)
        ch = self.results(consumers)
        self.tell(producers[:1], "PROBE")
        while not first_ack.wait(0.5):
            self.alive_or_raise()
            if self.remaining() < 150:
                raise RunFailed("no produce was acked\n"
                                + self.tail("produce-0"))
        t_first = time.monotonic_ns()
        t_warm = self.await_warm()
        self.tell(consumers + producers, "GO")
        log(f"first ack after {(t_first - self.t_start_ns) / 1e9:.1f}s, the "
            f"broker's warm-up over {(t_warm - t_first) / 1e9:.1f}s later; "
            f"warming {cell['warm_s']}s with the cell's traffic")
        t0 = t_warm + int(float(cell["warm_s"]) * 1e9)
        t1 = t0 + int(self.seconds * 1e9)
        self.tell(producers + consumers, f"WINDOW {t0} {t1}")
        setup_s = (t0 - self.t_start_ns) / 1e9

        snapshots: list = []
        sampler_stop = threading.Event()
        if self.trace:
            from benchmarks.readers._common import parse_exposition

            def sample() -> None:
                from ripplemq_tpu.wire.transport import TcpClient

                rpc = TcpClient()
                while not sampler_stop.is_set():
                    try:
                        r = rpc.call(self.bootstrap[0],
                                     {"type": "admin.metrics_text"},
                                     timeout=10.0)
                        snapshots.append((time.monotonic_ns(),
                                          parse_exposition(r["text"])))
                    except Exception:
                        pass
                    sampler_stop.wait(0.25)
                rpc.close()

            threading.Thread(target=sample, daemon=True).start()
        while time.monotonic_ns() < t1:
            self.alive_or_raise()
            if self.trace and time.monotonic_ns() >= t0 + int(
                    min(2.0, self.seconds / 4) * 1e9):
                flag = os.path.join(self.work, "profile.start")
                if not os.path.exists(flag):
                    open(flag, "w").close()
            time.sleep(0.1)
        prod = self.collect(producers, ph, "produce")
        sampler_stop.set()
        records = load_records(self.work)
        ref = ReferenceLog(self.seed, self.size, records, len(self.streams))
        whole = cell["delivery"] == "whole"
        t_drain = time.monotonic_ns()
        if whole:
            expect = os.path.join(self.work, "expect.npy")
            np.save(expect, ref.counts)
            self.tell(consumers, f"DRAIN "
                      f"{t_drain + int(cell['drain_limit_s'] * 1e9)} {expect}")
        else:
            self.tell(consumers, f"DRAIN {t_drain} -")
        cons = self.collect(consumers, ch, "consume")
        t_drained = time.monotonic_ns()

        # ---- broker side: spans, stats, the trace; then a clean stop
        spans: list = []
        trace_summary = None
        if self.trace:
            for i in range(self.n_brokers):
                after = -1
                while True:
                    r = self._rpc.call(self.bootstrap[i], {
                        "type": "admin.spans", "after": after,
                        "max_spans": 4096}, timeout=30.0)
                    spans.extend(r["spans"])
                    if r["cursor"] == after or not r["spans"]:
                        break
                    after = r["cursor"]
            for i in range(len(producers)):
                with open(os.path.join(
                        self.work, f"spans-client-{i}.json")) as f:
                    spans.extend(json.load(f))
            path = os.path.join(self.work, "trace_summary.json")
            until = time.monotonic() + 60
            while not os.path.exists(path) and time.monotonic() < until:
                time.sleep(0.1)
            if not os.path.exists(path):
                raise RunFailed("broker 0 wrote no trace summary")
            with open(path) as f:
                trace_summary = json.load(f)
            if "error" in trace_summary:
                raise RunFailed(f"profiler: {trace_summary['error']}")
        stat_errors = []
        replicas = None
        device = None
        for i in range(self.n_brokers):
            st = self.stats(i)
            for key, want in STAT_KEYS:
                if st[key] != want and not (self.rehearse
                                            and key == "store_native"):
                    stat_errors.append(f"broker {i}: {key} = {st[key]!r}")
            ctl = st["controller"]
            if ctl["id"] != 0 or ctl["epoch"] != boot_epoch:
                stat_errors.append(
                    f"broker {i}: controller moved to {ctl['id']} epoch "
                    f"{ctl['epoch']} (booted 0/{boot_epoch})")
            if i == 0:
                leaders_at_end = self.leaders(st)
                log(f"partition leaders by broker: at ready "
                    f"{self.tally(leaders_at_boot)}, at the end "
                    f"{self.tally(leaders_at_end)}")
                if leaders_at_end != leaders_at_boot:
                    log("  (partition leadership moved during the run: the "
                        "program's placement is sticky but boot-time "
                        "elections are not; recorded, judges nothing)")
                e = st["engine"]
                if e is None:
                    stat_errors.append("broker 0 lost its engine")
                    continue
                device = e["device"]
                if e["step_errors"] != 0:
                    stat_errors.append(f"step_errors = {e['step_errors']}")
                replicas = sorted({0, *ctl["standbys"]})
        self.stopping = True
        for b in self.brokers:
            b.send_signal(signal.SIGTERM)
        for i, b in enumerate(self.brokers):
            try:
                rc = b.wait(timeout=max(5.0, min(120.0, self.remaining())))
            except subprocess.TimeoutExpired:
                rc = None
            if rc != 0:
                stat_errors.append(f"broker {i} did not stop cleanly (rc={rc})")
                log(self.tail(f"broker-{i}"))

        # ---- (b) every configured replica's data dir against the reference
        want_replicas = int(cfg["guarantees"]["replicas"])
        replicas = replicas or []
        if "short_replica" in self.faults and replicas:
            self._cut_tail(replicas[-1])
        eng = cfg["cluster"]["engine"]
        scans = [self.start_role("scan", f"scan-{i}", dict(
            self.child_spec("producers", 0), slot_bytes=eng["slot_bytes"],
            store_dir=os.path.join(self.work, f"broker-{i}", "segments")))
            for i in replicas]
        self.await_ready(scans)
        self.tell(scans, "GO")
        sh = self.results(scans)

        # ---- (a) what every subscription received, meanwhile
        subs = self.subscriptions
        got: dict = {}  # subscription -> stream -> bytes
        lat, lat_stamp, lat_sub = [], [], []
        for i, r in enumerate(cons):
            for name in r["received_by_t1"]:  # its consumers reported
                got.setdefault(name, {})
            recv = os.path.join(self.work, f"recv-{i}.")
            flat = np.load(recv + "bytes.npy")
            at = 0
            for q, s, n in np.load(recv + "index.npy"):
                got[subs[int(q)]][int(s)] = flat[at:at + int(n)]
                at += int(n)
            lat.append(np.load(recv + "lat.npy"))
            lat_stamp.append(np.load(recv + "latstamp.npy"))
            lat_sub.append(np.load(recv + "latsub.npy"))
        lat = np.concatenate(lat)
        lat_stamp = np.concatenate(lat_stamp)
        lat_sub = np.concatenate(lat_sub)
        dl = compare_subscriptions(ref, got, subs, prefix_ok=not whole)
        received_bytes = sum(len(b) for g in got.values() for b in g.values())
        reported = set(got)
        del got, flat
        scanned = self.collect(scans, sh, "scan")

        # ---- the window's own numbers
        in_win = (records["stamp"] >= t0) & (records["stamp"] < t1)
        ack_ms = (records["ack"][in_win] - records["stamp"][in_win]) / 1e6
        acked_in_win = (records["ack"] >= t0) & (records["ack"] < t1)
        acked_msgs = int(records["n"][acked_in_win].sum())
        attempted = sum(r["due_msgs"] for r in prod)
        acked_due = int(records["n"][in_win].sum())
        failed = max(0, attempted - acked_due)
        # a message counts once a subscription; one that any subscription
        # lacks is a failed operation (the one furthest short says how many)
        delivered_due = np.bincount(lat_sub, minlength=len(subs))
        if whole:
            failed += max(0, acked_due - int(delivered_due.min()))
        acked_by_t1 = int(records["n"][records["ack"] < t1].sum())
        lag = {name: acked_by_t1 - sum(r["received_by_t1"].get(name, 0)
                                       for r in cons) for name in subs}
        late = [x for r in prod for x in r.get("late_ms", [])]
        log(f"window: {attempted} messages due or sent, {acked_due} of them "
            f"acked ({int(in_win.sum())} calls), {acked_msgs} messages acked "
            f"inside the window; subscription lag at window end "
            f"{' '.join(f'{n}={v}' for n, v in lag.items())} messages; "
            f"{received_bytes} bytes received in all; drain took "
            f"{(t_drained - t1) / 1e9:.1f}s")

        steps = cell["producers"]["params"].get("rate_steps_msgs_per_s")
        if steps:  # the builder's knee sweep: one line per offered rate
            edges = np.linspace(t0, t1, len(steps) + 1)
            for k, rate in enumerate(steps):
                m = in_win & (records["stamp"] >= edges[k]) & (
                    records["stamp"] < edges[k + 1])
                a = (records["ack"][m] - records["stamp"][m]) / 1e6
                st = records["stamp"][m]
                third = (edges[k + 1] - edges[k]) / 3
                early = a[st < edges[k] + third]
                lateq = a[st >= edges[k + 1] - third]
                d = lat[(lat_stamp >= edges[k]) & (lat_stamp < edges[k + 1])]
                due = int(round(rate * (edges[k + 1] - edges[k]) / 1e9))
                log(f"sweep step {k}: offered {rate} msgs/s, acked calls "
                    f"{len(a)} ({int(records['n'][m].sum())} of ~{due} msgs),"
                    f" ack p50 {np.median(a) if len(a) else -1:.1f} p99 "
                    f"{np.percentile(a, 99) if len(a) else -1:.1f} ms, "
                    f"first third p50 "
                    f"{np.median(early) if len(early) else -1:.1f} last "
                    f"third p50 {np.median(lateq) if len(lateq) else -1:.1f}"
                    f"; delivered {len(d)} p50 "
                    f"{np.median(d) if len(d) else -1:.1f} p99 "
                    f"{np.percentile(d, 99) if len(d) else -1:.1f} ms")

        # ---- compiles inside the window
        allowed = cfg.get("steady_state_compiles", {})
        unexpected, expected = [], []
        cpath = os.path.join(self.work, "compiles.jsonl")
        if os.path.exists(cpath):
            with open(cpath) as f:
                for line in f:
                    ev = json.loads(line)
                    if t0 <= ev["t_ns"] < t1 and ev["msg"].startswith(
                            "Compiling "):
                        (expected if any(a in ev["msg"] for a in allowed)
                         else unexpected).append(ev["msg"][:80])
        log(f"compiles inside the window: {len(unexpected)} unexpected "
            f"{unexpected[:3]}, {len(expected)} of the configuration's "
            f"steady-state list")

        # ---- correct: every number beside its limit
        N = self.numbers
        N.extend(delivery_numbers(dl, whole,
                                  int(cfg["deployment"]["subscriptions"])))
        N.append(("delivery.consumer_errors",
                  sum(len(r["errors"]) + r["ragged_chunks"] for r in cons),
                  "0"))
        N.append(("reference.duplicate_offsets", ref.duplicate_offsets, "0"))
        N.append(("replicas.scanned", len(replicas), f"== {want_replicas}"))
        for i, r in zip(replicas, scanned):
            for k in ("differ", "missing", "extra"):
                N.append((f"replica{i}.{k}", r[k], "0"))
        N.append(("brokers.stat_errors", len(stat_errors), "0"))
        N.append(("window.unexpected_compiles", len(unexpected), "0"))
        N.append(("producers.failed_calls",
                  sum(r["failed_calls_total"] for r in prod), "0"))
        correct = not failing(N)
        for line in compared_lines(N):
            log(line)
        for e in stat_errors[:6] + [e for r in prod for e in r["errors"]][:4] \
                + [e for r in cons for e in r["errors"]][:4]:
            log(f"  error: {e}")
        for name in dl["bad"]:
            r = dl["by_subscription"][name]
            log(f"  delivery to subscription {name}: differ {r['differ']} "
                f"extra {r['extra']} missing {r['missing']} lag {r['lag']} "
                f"on streams {r['bad_streams']}"
                + ("" if name in reported else
                   " (no consumer of it reported)"))

        mid = (t0 + t1) // 2
        for label, xs, st in (("produce ack", ack_ms, records["stamp"][in_win]),
                              ("delivery", lat, lat_stamp)):
            if len(xs) > 1:
                log(f"{label} median by window half (ms): first "
                    f"{np.median(xs[st < mid]):.3f}, second "
                    f"{np.median(xs[st >= mid]):.3f}")
            if len(xs):
                q = np.percentile(xs, [50, 75, 90, 95, 99])
                log(f"{label} percentiles over {len(xs)} samples (ms): p50 "
                    f"{q[0]:.3f} p75 {q[1]:.3f} p90 {q[2]:.3f} p95 "
                    f"{q[3]:.3f} p99 {q[4]:.3f} mean {np.mean(xs):.3f}")

        # ---- metrics
        e2e = {}
        names = cell["end_to_end"]
        for name in names:
            series = {"produce_ack": ack_ms, "deliver": lat}.get(
                name.rsplit("_p", 1)[0])
            if series is None or not len(series):
                continue
            asked = float(name.rsplit("_p", 1)[1].removesuffix("_ms"))
            if asked == 50:
                e2e[name] = (bstats.median(series), "ms")
            else:
                v, pct, n = bstats.tail(series, asked)
                log(f"{name}: {n} samples, tail taken at p{pct:g}")
                e2e[name] = (v, "ms")
        if "acked_msgs_per_s" in names:
            e2e["acked_msgs_per_s"] = (acked_msgs / self.seconds, "msgs/s")
        e2e["setup_s"] = (setup_s, "s")
        if late:
            log(f"generator lateness: p50 {bstats.median(late):.3f} ms, max "
                f"{max(late):.3f} ms over {len(late)} calls")

        metrics = e2e
        dev_out = {"platform": device["platform"] if device else None,
                   "kind": device["device_kind"] if device else None,
                   "count": device["device_count"] if device else None,
                   "memory_peak_bytes": device["peak_bytes_in_use"]
                   if device else None}
        out = {"correct": bool(correct), "attempted": int(attempted),
               "failed": int(failed)}
        if self.trace:
            run = {"t0_ns": t0, "t1_ns": t1, "snapshots": snapshots,
                   "spans": spans, "trace": trace_summary, "config": cfg,
                   "cell": cell, "peaks": self.peaks,
                   "client": {"late_ms": late, "ack_ms": ack_ms.tolist(),
                              "deliver_ms": lat,
                              "deliver_ms_by_subscription": {
                                  name: lat[lat_sub == q]
                                  for q, name in enumerate(subs)}}}
            metrics = {}
            for m in self.layer_metrics:
                reader = importlib.import_module(
                    f"benchmarks.readers.{m['reader']['kind']}")
                v = reader.read(m["reader"]["args"], run)
                if v is None:
                    log(f"per-layer {m['name']}: nothing to read")
                    continue
                metrics[m["name"]] = (float(v), m["unit"])
            dev_out["busy_s"] = trace_summary["busy_s"]
            dev_out["window_s"] = trace_summary["window_s"]
            out["breakdown"] = {"device_ops": trace_summary["device_ops"],
                                "idle_gaps": trace_summary["idle_gaps"]}
            log(f"trace: {trace_summary['devices_traced']} device(s), busy "
                f"{trace_summary['busy_s']:.4f}s of "
                f"{trace_summary['window_s']:.4f}s; profiler start cost "
                f"{trace_summary['start_cost_s']:.2f}s; modules "
                f"{json.dumps(trace_summary['modules'])[:600]}")
            log(f"trace lines: {json.dumps(trace_summary['plane_lines'])[:800]}")
            log(f"end-to-end in the traced run (not reported): "
                f"{ {k: round(v[0], 3) for k, v in e2e.items()} }")
            log(f"spans: {len(spans)} records")
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:  # the builder's look at one trace by hand
                os.makedirs(keep, exist_ok=True)
                with open(os.path.join(keep, "trace_summary.json"), "w") as f:
                    json.dump(trace_summary, f)
                for name in ("compiles.jsonl", "broker-0.stderr"):
                    shutil.copy(os.path.join(self.work, name), keep)
        out["metrics"] = {k: {"value": v, "unit": u}
                          for k, (v, u) in metrics.items()}
        out["device"] = dev_out
        out["cold"] = self.cold
        out.update(verdict(N, {
            "drain_s": (t_drained - t_drain) / 1e9,
            "drain.deadline_reached":
                int(sum(r["short_at_deadline"] for r in cons) > 0)}))
        return out

    def _cut_tail(self, broker: int) -> None:
        """fault short_replica: one replica's newest segment loses its end."""
        d = os.path.join(self.work, f"broker-{broker}", "segments")
        segs = sorted(f for f in os.listdir(d) if f.startswith("segment-")
                      and f.endswith(".log")
                      and os.path.getsize(os.path.join(d, f)) > 0)
        path = os.path.join(d, segs[-1])
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - min(size // 2, 1 << 16))
        log(f"fault short_replica: cut {path} from {size} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="dry run on the CPU backend at the files' rehearsal "
                         "sizes; prints no result on stdout, exits 4")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "ripplemq_tpu")):
        print("the program under test is not in this directory",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    try:
        run = Run(args.workload, args.seed, args.seconds, bool(args.trace),
                  rehearse=args.rehearse)
        out = run.run()
    except RunFailed as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return EXIT_FAILED
    line = json.dumps(out)
    for text in compared_lines(run.numbers):  # stderr ends with them too
        print(text, file=sys.stderr, flush=True)
    if args.rehearse:
        print(f"rehearsal (no device metric, not a result): {line}",
              file=sys.stderr, flush=True)
        return EXIT_REHEARSAL
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
