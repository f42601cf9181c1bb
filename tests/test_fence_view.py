"""The standby stream's fence view (ISSUE 28): `PartitionManager`
publishes controller, epoch and standby set as ONE immutable triple that
`RoundReplicator.begin`, the sender's frame stamp, `wait` and the
standby's `repl.rounds` refusals read WITHOUT `PartitionManager.lock` —
the lock every consume and commit handler queues on.

Held here: (1) a reader never sees a triple no apply produced, (2) the
stream's hot path acquires that lock zero times and stamps no settled
floor where nothing reads it, (3) a sender whose view shows another
controller fences its group and never stamps the successor's epoch, (4)
with follower reads on the frame carries its floors as before, (5)
`repl.send_wait_us` is observed once per acked group."""

from __future__ import annotations

import threading
import types

import pytest

from ripplemq_tpu.broker.manager import (
    OP_SET_CONTROLLER,
    OP_SET_STANDBYS,
    FenceView,
    PartitionManager,
)
from ripplemq_tpu.broker.replication import FencedError, RoundReplicator
from ripplemq_tpu.broker.server import BrokerServer
from ripplemq_tpu.chaos.cluster import InProcCluster, make_cluster_config
from ripplemq_tpu.metadata.models import Topic
from ripplemq_tpu.obs.metrics import Metrics
from ripplemq_tpu.wire.transport import RpcError
from tests.helpers import wait_until
from tests.test_repl_pipeline import REC, PipelinedStubClient


def _mk_manager(broker_id=0):
    return PartitionManager(broker_id, make_cluster_config(n_brokers=4))


def _fields(m):
    return (m.controller_broker, m.controller_epoch, m.standbys)


# ---------------------------------------------------- the view itself


@pytest.mark.parametrize("site", ["set_controller", "set_standbys",
                                  "stale_ops_ignored", "restore"])
def test_every_apply_site_publishes_the_view(site):
    """The three sites that change controller, epoch or standby set
    leave the view equal to the fields; an ignored (stale) command
    leaves both as they were."""
    m = _mk_manager()
    assert m.fence_view == FenceView(m.config.controller, 0, ())
    m.apply(1, {"op": OP_SET_CONTROLLER, "controller": 1, "epoch": 1,
                "standbys": [1, 2, 3]})
    if site == "set_controller":
        assert m.fence_view == (1, 1, (2, 3)) == _fields(m)
    elif site == "set_standbys":
        m.apply(2, {"op": OP_SET_STANDBYS, "epoch": 1, "standbys": [1, 3]})
        assert m.fence_view == (1, 1, (3,)) == _fields(m)
    elif site == "stale_ops_ignored":
        m.apply(2, {"op": OP_SET_CONTROLLER, "controller": 2, "epoch": 1,
                    "standbys": [0]})
        m.apply(3, {"op": OP_SET_STANDBYS, "epoch": 0, "standbys": [0]})
        assert m.fence_view == (1, 1, (2, 3)) == _fields(m)
    else:
        m2 = _mk_manager()
        m2.restore(m.snapshot())
        assert m2.fence_view == (1, 1, (2, 3)) == _fields(m2)
    assert isinstance(m.fence_view, FenceView)


def test_reader_never_sees_a_triple_no_apply_produced():
    """A reader spinning on the view while handovers and standby-set
    rewrites alternate sees only triples some apply left: controller and
    epoch of ONE handover (here controller == epoch % 4), never a
    standby set holding its controller, never a set from another epoch
    (here every set of epoch e is drawn from {e+1, e+2, e+3} mod 4)."""
    m = _mk_manager()
    produced = {m.fence_view}
    seen: set = set()
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            seen.add(m.fence_view)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    idx = 0
    for epoch in range(1, 1500):
        ctrl = epoch % 4
        others = [(epoch + k) % 4 for k in (1, 2, 3)]
        idx += 1
        # The handover names its own controller among the standbys (the
        # apply strips it) and assigns the fields one by one.
        m.apply(idx, {"op": OP_SET_CONTROLLER, "controller": ctrl,
                      "epoch": epoch, "standbys": others + [ctrl]})
        produced.add(m.fence_view)
        idx += 1
        m.apply(idx, {"op": OP_SET_STANDBYS, "epoch": epoch,
                      "standbys": others[:1 + epoch % 2]})
        produced.add(m.fence_view)
    stop.set()
    t.join(timeout=10)
    assert len(seen) > 1, "the reader never overlapped the applies"
    assert seen <= produced, sorted(seen - produced)[:5]
    for v in seen:
        assert v.controller == v.epoch % 4
        assert v.controller not in v.standbys


# ----------------------------------- the wiring, without a whole broker


def _wired_replicator(mgr, client, follower_reads=False, depth=4,
                      metrics=None, floors=None):
    """`BrokerServer._make_replicator` over a stand-in that has just the
    attributes it reads: the real wiring around a real manager."""
    config = types.SimpleNamespace(
        rpc_timeout_s=5.0, repl_pipeline_depth=depth, replication="full",
        follower_reads=follower_reads,
    )
    ns = types.SimpleNamespace(
        manager=mgr, broker_id=mgr.broker_id, config=config,
        metrics=metrics, client=client, _addr_of=lambda b: f"b{b}",
        _settle_floors_stamp=floors,
    )
    return BrokerServer._make_replicator(ns)


class HandoverOnTheWireClient:
    """Synchronous transport (no call_async): the first frame's call
    returns only when the test lets it, after `on_first` ran — a
    metadata apply landing while frame 0 is on the wire."""

    def __init__(self, on_first) -> None:
        self.frames: list[dict] = []
        self.first_sent = threading.Event()
        self.let_go = threading.Event()
        self._on_first = on_first

    def call(self, addr, request, timeout=None):
        self.frames.append(request)
        if len(self.frames) == 1:
            self.first_sent.set()
            assert self.let_go.wait(10.0)
            self._on_first()
        return {"ok": True}


def test_deposed_between_two_frames_fences_and_never_stamps_successor():
    m = _mk_manager()
    m.apply(1, {"op": OP_SET_STANDBYS, "epoch": 0, "standbys": [1]})

    def handover():
        m.apply(2, {"op": OP_SET_CONTROLLER, "controller": 1, "epoch": 1,
                    "standbys": [2]})

    client = HandoverOnTheWireClient(handover)
    rep = _wired_replicator(m, client, depth=1)
    try:
        t1 = rep.begin(REC)
        assert client.first_sent.wait(10.0)
        t2 = rep.begin([(0, 1, 8, b"second-frame")])  # queued behind 0
        client.let_go.set()
        # Frame 0 was acked under epoch 0 (its standby took it before
        # the handover); the second group finds another controller in
        # its ONE view and fails without a frame.
        with pytest.raises(FencedError):
            t2.futs[1].result(timeout=10.0)
        assert t1.futs[1].result(timeout=10.0) is True
        assert [f["epoch"] for f in client.frames] == [0]
        # wait() fences too: standby 1 left the set in the SAME view
        # that deposes this broker (it is the promoted one).
        with pytest.raises(FencedError):
            rep.wait(t1, timeout_s=5.0)
        with pytest.raises(FencedError):
            rep.begin(REC)
        assert m.fence_view == (1, 1, (2,))
    finally:
        rep.stop()


@pytest.mark.parametrize("follower_reads", [False, True])
def test_frame_carries_floors_only_where_a_plane_reads_them(follower_reads):
    m = _mk_manager()
    m.apply(1, {"op": OP_SET_STANDBYS, "epoch": 0, "standbys": [1]})
    asked: list = []

    def floors(slots):
        asked.append(list(slots))
        return [[s, 7, []] for s in slots]

    client = PipelinedStubClient()
    rep = _wired_replicator(m, client, follower_reads=follower_reads,
                            floors=floors)
    try:
        t = rep.begin(REC)
        frame = client.wait_sent(1)[0]
        client.resolve(0, {"ok": True})
        rep.wait(t, timeout_s=5.0)
        if follower_reads:
            assert frame["floors"] == [[0, 7, []]] and asked == [[0]]
        else:
            assert "floors" not in frame and asked == []
        assert frame["epoch"] == 0 and frame["sender"] == 0
    finally:
        rep.stop()


def test_send_wait_observed_once_per_acked_group():
    m = _mk_manager()
    m.apply(1, {"op": OP_SET_STANDBYS, "epoch": 0, "standbys": [1]})
    metrics = Metrics()
    client = PipelinedStubClient()
    rep = _wired_replicator(m, client, metrics=metrics, depth=2)
    try:
        t1 = rep.begin(REC)
        client.wait_sent(1)
        t2 = rep.begin(REC)
        client.wait_sent(2)
        t3 = rep.begin(REC)  # window full: waits in the sender's queue
        # Frame 0 dies on the wire: both in-flight groups rewind and go
        # out again with the queued round, as ONE group (a lost attempt
        # is not an acked group and observes nothing).
        client.resolve(0, RpcError("conn reset"))
        frames = client.wait_sent(3)
        assert len(frames[2]["records"]) == 3
        client.resolve(2, {"ok": True})
        for t in (t1, t2, t3):
            rep.wait(t, timeout_s=5.0)
        t4 = rep.begin(REC)
        client.wait_sent(4)
        client.resolve(3, {"ok": True})
        rep.wait(t4, timeout_s=5.0)
        snap = metrics.snapshot()
        assert snap["counters"]["repl.frames"] == 2
        hist = snap["histograms"]
        assert hist["repl.send_wait_us"]["count"] == 2
        assert hist["repl.frame_us"]["count"] == 2
        assert hist["repl.group_rounds"]["count"] == 2
    finally:
        rep.stop()


# --------------------------------------- the hot path on a live cluster


class CountingLock:
    """Stand-in for `PartitionManager.lock`: the real lock, plus a count
    of the acquisitions made on the standby stream's path — by a
    `repl-sender-*` thread, or by any thread while it is inside one of
    the calls marked with `inside()`."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self._tls = threading.local()
        self.on_stream: list[str] = []

    def inside(self, label, fn):
        def marked(*a, **kw):
            prev = getattr(self._tls, "label", None)
            self._tls.label = label
            try:
                return fn(*a, **kw)
            finally:
                self._tls.label = prev
        return marked

    def _note(self) -> None:
        label = getattr(self._tls, "label", None)
        name = threading.current_thread().name
        if label is None and name.startswith("repl-sender-"):
            label = name
        if label is not None:
            self.on_stream.append(label)

    def acquire(self, *a, **kw):
        self._note()
        return self._inner.acquire(*a, **kw)

    def release(self):
        return self._inner.release()

    def __enter__(self):
        self._note()
        return self._inner.__enter__()

    def __exit__(self, *exc):
        return self._inner.__exit__(*exc)


@pytest.mark.parametrize("follower_reads", [False, True])
def test_stream_hot_path_takes_no_manager_lock(follower_reads, monkeypatch):
    """Rounds produced through a live in-proc cluster: `begin`, the
    sender threads and `wait` on the controller, and `repl.rounds` on
    each standby, acquire `PartitionManager.lock` ZERO times; with
    follower reads off `DataPlane.settle_floors` is never called and no
    frame carries `floors`. With them on, the frame carries what it
    always did (the stamp's own locked reads included)."""
    config = make_cluster_config(
        n_brokers=3, topics=(Topic("t", 1, 3),),
        follower_reads=follower_reads,
    )
    with InProcCluster(config) as c:
        c.wait_for_leaders()
        ctrl = c.brokers[c.config.controller]
        assert wait_until(
            lambda: len(ctrl.manager.current_standbys()) == 2, timeout=60
        ), "standby set never reached 2"
        client = c.client("fv")
        req = {"type": "produce", "topic": "t", "partition": 0,
               "messages": [b"warm"]}
        assert wait_until(
            lambda: client.call(ctrl.addr, req, timeout=5.0).get("ok"),
            timeout=60,
        )
        # Instrument: the counting lock on every manager, the marks on
        # the controller's begin / wait and the standbys' handler, a
        # spy on settle_floors and on the frames the stream sends.
        dp, rep = ctrl.dataplane, ctrl._replicator
        locks = {}
        for bid, b in c.brokers.items():
            locks[bid] = CountingLock(b.manager.lock)
            monkeypatch.setattr(b.manager, "lock", locks[bid])
        lk = locks[ctrl.broker_id]
        calls = {"begin": 0, "wait": 0, "floors": 0}

        def counted(name, fn):
            def f(*a, **kw):
                calls[name] += 1
                return fn(*a, **kw)
            return f

        monkeypatch.setattr(dp, "replicate_begin_fn",
                            lk.inside("begin", counted("begin", rep.begin)))
        monkeypatch.setattr(dp, "replicate_wait_fn",
                            lk.inside("wait", counted("wait", rep.wait)))
        monkeypatch.setattr(dp, "settle_floors",
                            counted("floors", dp.settle_floors))
        frames: list[dict] = []
        for bid, b in c.brokers.items():
            if bid == ctrl.broker_id:
                continue

            def handler(r, _b=b, _lk=locks[bid],
                        _h=b._handle_repl_rounds):
                frames.append(r)
                return _lk.inside(f"repl.rounds@{_b.broker_id}", _h)(r)

            monkeypatch.setattr(b, "_handle_repl_rounds", handler)
        for i in range(6):
            r = client.call(ctrl.addr, dict(req, messages=[b"m%d" % i]),
                            timeout=10.0)
            assert r.get("ok"), r
        assert calls["begin"] >= 1 and calls["wait"] >= 1
        data = [f for f in frames if f["records"]]
        assert len(data) >= 2  # both standbys took the rounds
        assert all(f["epoch"] == 0 and f["sender"] == ctrl.broker_id
                   for f in frames)
        standby_takes = [x for bid, l in locks.items()
                         if bid != ctrl.broker_id for x in l.on_stream]
        if follower_reads:
            assert all("floors" in f for f in data)
            assert calls["floors"] >= len(data)
            # begin and wait stay off the lock even here; only the
            # sender's floor stamp (BrokerServer._local_engine) and the
            # standby's follower plane take what they took before.
            assert not [x for x in lk.on_stream
                        if x in ("begin", "wait")], lk.on_stream
        else:
            assert not any("floors" in f for f in frames)
            assert calls["floors"] == 0
            assert lk.on_stream == []
            assert standby_takes == []


def test_three_callable_plane_keeps_the_check_stamp_check():
    """A plane built from three separate callables (no `fence_fn`) still
    reads the epoch between two active checks: a deposition that lands
    around the epoch read yields an inactive view, never the successor's
    epoch under `active`."""
    state = {"active": True, "epoch": 3}

    def epoch():
        # The handover lands exactly here: after the first active
        # check, before the second.
        state["active"], state["epoch"] = False, 4
        return 4

    rep = RoundReplicator(
        PipelinedStubClient(), addr_of=lambda b: f"b{b}",
        epoch_fn=epoch, members_fn=lambda: (1,),
        active_fn=lambda: state["active"], sender_id=0,
    )
    try:
        active, _, _ = rep.fence()
        assert active is False
        with pytest.raises(FencedError):
            rep.begin(REC)
    finally:
        rep.stop()
