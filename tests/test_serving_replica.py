"""Horizontal serving: replica fan-out, least-loaded routing,
disaggregated prefill/decode, request migration.

The spine of this suite is the CROSS-PROCESS parity contract
(docs/SERVING.md "Horizontal serving"): a stream served by any replica
of a model — including one handed off prefill→decode over the `DLFP`
frame, or migrated mid-flood off a killed replica — finishes bit-equal
to the single-process reference. Plus the wire-hardening contract:
every malformed frame decodes to one typed `WireFormatError`, never a
leaked `struct.error`/`KeyError`.
"""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.parallel.elastic import (
    ElasticCoordinator,
    serving_directory,
)
from deeplearning4j_tpu.serving import wire
from deeplearning4j_tpu.serving.disagg import (
    DecodeWorker,
    PrefillWorker,
    run_disaggregated,
)
from deeplearning4j_tpu.serving.replica import (
    ReplicaClient,
    ReplicaLostError,
    ReplicaManager,
    ReplicaSet,
    ReplicaWorker,
)
from deeplearning4j_tpu.serving.router import FleetRouter, MigratingStream
from deeplearning4j_tpu.serving.server import GenerationServer, ShedError
from deeplearning4j_tpu.zoo.transformer import TransformerLM, generate

V, D, HEADS, LAYERS, MAXLEN = 23, 16, 4, 2, 48
N_TOK = 8


def tiny_lm(seed=3):
    return TransformerLM(vocab_size=V, d_model=D, n_layers=LAYERS,
                         n_heads=HEADS, max_len=MAXLEN, seed=seed).init()


@pytest.fixture(scope="module")
def net():
    return tiny_lm()


@pytest.fixture(scope="module")
def prompts():
    return np.random.default_rng(5).integers(0, V, (6, 4))


@pytest.fixture(scope="module")
def ref(net, prompts):
    return generate(net, prompts, N_TOK, temperature=0)


@pytest.fixture()
def coord():
    c = ElasticCoordinator(settle_s=0.05, grace_s=1.0,
                           tick_s=0.05).start()
    yield c
    c.stop()


def _worker(net, addr, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_len", 4)
    return ReplicaWorker(net, model="m", version=1, coordinator=addr,
                         heartbeat_interval_s=0.05, **kw).start()


def _wait_replicas(rset, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rset.refresh(force=True)
        if len(rset.backends()) == n:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"replica set never reached {n} backends "
        f"({len(rset.backends())} live)")


# ===================================================== wire hardening
class TestWireHardening:
    def test_request_roundtrip(self):
        rng = np.asarray([3, 5], np.uint32)
        frame = wire.encode_request("m", "r1", [1, 2, 3], 7,
                                    temperature=0.5, top_p=0.9, rng=rng,
                                    emit_start=4, trace_id="t1")
        header, prompt = wire.decode_request(frame)
        assert header["model"] == "m" and header["request_id"] == "r1"
        assert header["n_tokens"] == 7 and header["emit_start"] == 4
        assert header["trace_id"] == "t1"
        np.testing.assert_array_equal(header["rng"], rng)
        np.testing.assert_array_equal(prompt, [1, 2, 3])

    def test_reply_roundtrip_and_error(self):
        frame = wire.encode_reply("r1", 2, [4, 5], done=True, model="m",
                                  version=3, error=ShedError("busy"))
        header, toks = wire.decode_reply(frame)
        assert header["seq"] == 2 and header["done"]
        np.testing.assert_array_equal(toks, [4, 5])
        err = wire.reply_error(header)
        assert isinstance(err, ShedError) and "busy" in str(err)

    @pytest.mark.parametrize("mutate", [
        lambda f: f[:6],                              # truncated
        lambda f: b"XXXX" + f[4:],                    # unknown magic
        lambda f: wire.REPLY_MAGIC + f[4:],           # wrong known magic
        lambda f: f[:4] + struct.pack("<I", 1 << 28) + f[8:],  # hlen lie
        lambda f: f[:8] + b"\xff" * 16 + f[24:],      # garbage JSON
        lambda f: f[:-5],                             # cut ndarray bytes
        lambda f: 12345,                              # not bytes at all
    ])
    def test_corruption_is_typed(self, mutate):
        frame = wire.encode_request("m", "r", [1, 2], 3)
        with pytest.raises(wire.WireFormatError):
            wire.decode_request(mutate(frame))

    def test_non_dict_header_typed(self):
        bad = wire.REQUEST_MAGIC + struct.pack("<I", 2) + b"[]"
        with pytest.raises(wire.WireFormatError, match="JSON object"):
            wire.decode_request(bad)

    def test_missing_fields_typed(self):
        frame = wire.encode_request("m", "r", [1], 1)
        payload = frame[8 + struct.unpack_from("<I", frame, 4)[0]:]
        bad = wire.REQUEST_MAGIC + struct.pack("<I", 2) + b"{}" + payload
        with pytest.raises(wire.WireFormatError, match="missing"):
            wire.decode_request(bad)

    def test_malformed_rng_typed(self):
        import json
        hdr = json.dumps({"model": "m", "request_id": "r",
                          "n_tokens": 1, "rng": ["x", "y"]}).encode()
        frame = wire.encode_request("m", "r", [1], 1)
        payload = frame[8 + struct.unpack_from("<I", frame, 4)[0]:]
        bad = wire.REQUEST_MAGIC + struct.pack("<I", len(hdr)) + hdr \
            + payload
        with pytest.raises(wire.WireFormatError, match="rng"):
            wire.decode_request(bad)

    def test_handoff_requires_kv_shape(self):
        header = {k: 0 for k in wire.HANDOFF_FIELDS}
        header["block_len"] = 4
        with pytest.raises(wire.WireFormatError, match="stacked K/V"):
            wire.decode_handoff(wire._frame(
                wire.HANDOFF_MAGIC, header, np.zeros((2, 3), np.float32)))

    def test_socket_framing_roundtrip_and_bound(self):
        a, b = socket.socketpair()
        try:
            frame = wire.encode_reply("r", 0, [1, 2, 3], done=False)
            wire.send_frame(a, frame)
            assert wire.recv_frame(b) == frame
            # corrupt length prefix past the wire bound: typed
            a.sendall(struct.pack("<I", wire.MAX_FRAME_BYTES + 1))
            with pytest.raises(wire.WireFormatError, match="bound"):
                wire.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_recv_frame_peer_close(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(ConnectionError):
                wire.recv_frame(b)
        finally:
            b.close()


# ================================================ disaggregated PFD
class TestDisaggregation:
    def test_split_pipeline_greedy_parity(self, net, prompts, ref):
        pre = PrefillWorker(net, n_slots=4, n_blocks=48, block_len=4)
        dec = DecodeWorker(net, n_slots=6, n_blocks=64, block_len=4)
        out = run_disaggregated(pre, dec, list(prompts), N_TOK)
        for got, want in zip(out, ref):
            np.testing.assert_array_equal(got, want)

    def test_split_pipeline_over_socket(self, net, prompts, ref):
        pre = PrefillWorker(net, n_slots=4, n_blocks=48, block_len=4)
        dec = DecodeWorker(net, n_slots=6, n_blocks=64, block_len=4)
        tx, rx = socket.socketpair()
        try:
            out = run_disaggregated(pre, dec, list(prompts[:3]), N_TOK,
                                    channel=(tx, rx))
        finally:
            tx.close()
            rx.close()
        for got, want in zip(out, ref[:3]):
            np.testing.assert_array_equal(got, want)

    def test_single_token_needs_no_handoff(self, net, prompts):
        pre = PrefillWorker(net, n_slots=2, n_blocks=16, block_len=4)
        first, frame = pre.prefill(prompts[0], 1)
        assert frame is None
        want = generate(net, prompts[:1], 1, temperature=0)[0]
        assert [first] == [int(t) for t in want]

    def test_adopt_rejects_block_len_mismatch(self, net, prompts):
        pre = PrefillWorker(net, n_slots=2, n_blocks=16, block_len=4)
        _, frame = pre.prefill(prompts[0], N_TOK)
        dec = DecodeWorker(net, n_slots=2, n_blocks=16, block_len=8)
        with pytest.raises(ValueError, match="block_len"):
            dec.adopt(frame)


# ================================================= serving directory
class TestServingDirectory:
    def test_serving_members_skip_training_ranks(self, coord, net):
        from deeplearning4j_tpu.parallel.elastic import ElasticClient
        trainer = ElasticClient(coord.address, "trainer-0",
                                heartbeat_interval_s=0.05)
        trainer.register(device_count=1)
        w = _worker(net, coord.address)
        try:
            deadline = time.monotonic() + 10
            status = {}
            while time.monotonic() < deadline:
                status = trainer.status()
                plan = status.get("plan") or {}
                if plan.get("serving_members") and plan.get("members"):
                    break
                time.sleep(0.05)
            plan = status["plan"]
            # the trainer keeps rank 0 of a world of ONE — serving
            # members never shift training ranks
            assert [m["token"] for m in plan["members"]] == ["trainer-0"]
            assert [m["token"] for m in plan["serving_members"]] \
                == [w.token]
            d = serving_directory(status, "m")
            assert len(d["replicas"]) == 1
            r = d["replicas"][0]
            assert r["port"] == w.port and r["version"] == 1
            assert set(r["load"]) >= {"queue_depth",
                                      "outstanding_tokens",
                                      "ewma_tok_s", "open_streams"}
        finally:
            w.stop()
            trainer.stop()

    def test_directory_filters_by_model(self, coord, net):
        w = _worker(net, coord.address)
        try:
            from deeplearning4j_tpu.parallel.elastic import retry_request
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                status = retry_request(coord.address,
                                       {"op": "status"})["status"]
                if serving_directory(status, "m")["replicas"]:
                    break
                time.sleep(0.05)
            assert serving_directory(status, "other")["replicas"] == []
        finally:
            w.stop()


# ==================================================== replica plane
class TestReplicaPlane:
    def test_round_trip_parity_and_version_tag(self, coord, net,
                                               prompts, ref):
        w = _worker(net, coord.address)
        client = ReplicaClient(w.host, w.port)
        try:
            streams = [client.submit("m", p, N_TOK) for p in prompts]
            for s, want in zip(streams, ref):
                np.testing.assert_array_equal(s.result(60), want)
                assert s.version == 1
                assert s.t_first is not None
        finally:
            client.close()
            w.stop()

    def test_mid_stream_death_is_typed(self, coord, net, prompts,
                                       monkeypatch):
        # the kill point is an event, not a sleep: every stream holds a
        # token and none can have finished. Warm, three streams of 24
        # tokens finish in 0.05 s (measured), before a 0.1 s sleep ends;
        # under the sandbox's step floor 40 tokens take 2 s
        monkeypatch.setenv("DL4J_SANDBOX_MODEL", "1")
        w = _worker(net, coord.address, dispatch_floor_s=0.05)
        client = ReplicaClient(w.host, w.port)
        try:
            streams = [client.submit("m", p, 40) for p in prompts[:3]]
            deadline = time.monotonic() + 60
            while not all(s.tokens for s in streams):
                assert time.monotonic() < deadline, "no first tokens"
                time.sleep(0.001)
            assert not any(s.done() for s in streams)
            w.stop()            # hard mid-stream death
            for s in streams:
                with pytest.raises(ReplicaLostError) as ei:
                    s.result(30)
                assert ei.value.request_id == s.request_id
                assert 0 <= ei.value.last_seq
                assert ei.value.tokens == s.tokens
                assert 1 <= len(s.tokens) < 40
        finally:
            client.close()
            w.stop()

    def test_emit_start_continuation_parity(self, net, prompts):
        """The migration seam itself: a sampled stream cut at K tokens
        resumes on a DIFFERENT server as prompt+received with
        emit_start=K, bit-equal to the uninterrupted stream."""
        rng = np.asarray([11, 17], np.uint32)
        a = GenerationServer(net, n_slots=2, n_blocks=32, block_len=4)
        a.start()
        try:
            full = a.generate_async(prompts[0], 10, temperature=0.8,
                                    rng=rng).result(60)
        finally:
            a.stop()
        k = 4
        b = GenerationServer(net, n_slots=2, n_blocks=32, block_len=4)
        b.start()
        try:
            head = list(full[:k])
            cont = b.generate_async(
                np.concatenate([prompts[0], full[:k]]), 10 - k,
                temperature=0.8, rng=rng, emit_start=k).result(60)
        finally:
            b.stop()
        np.testing.assert_array_equal(head + list(cont), full)


# ============================================= router: balance + shed
class TestRouterReplicated:
    def test_least_loaded_balance_and_parity(self, coord, net, prompts,
                                             ref):
        w1 = _worker(net, coord.address)
        w2 = _worker(net, coord.address)
        rset = ReplicaSet(coord.address, "m", refresh_s=0.05)
        router = FleetRouter()
        router.attach_replicas("m", rset)
        try:
            _wait_replicas(rset, 2)
            streams = [router.submit("m", p, N_TOK) for p in prompts]
            for s, want in zip(streams, ref):
                assert isinstance(s, MigratingStream)
                np.testing.assert_array_equal(s.result(60), want)
            assert {s.replica for s in streams} \
                == {w1.token, w2.token}
        finally:
            rset.close()
            w1.stop()
            w2.stop()

    def test_sheds_only_when_every_replica_is_past_budget(
            self, coord, net, prompts):
        w1 = _worker(net, coord.address)
        w2 = _worker(net, coord.address)
        rset = ReplicaSet(coord.address, "m", refresh_s=0.05)
        router = FleetRouter(max_queue=0)   # every replica reads "full"
        router.attach_replicas("m", rset)
        try:
            _wait_replicas(rset, 2)
            with pytest.raises(ShedError, match="all 2 live replicas"):
                router.submit("m", prompts[0], N_TOK)
            # raising max_queue admits again — balance before shed
            router.max_queue = 64
            s = router.submit("m", prompts[0], N_TOK)
            s.result(60)
        finally:
            rset.close()
            w1.stop()
            w2.stop()

    def test_kill_drill_zero_dropped_streams(self, coord, net, prompts,
                                             ref, monkeypatch):
        """Kill one of two replicas mid-flood: every accepted stream
        still finishes (migrated, greedy-bit-equal) and the set
        converges to the survivor."""
        # under the sandbox's step floor the flood outlasts the 0.1 s
        # before the kill (24 tokens take 0.5 s a stream); without it
        # twelve warm streams can finish first, the sooner since the
        # decode loop runs a step ahead (ISSUE 30), and nothing is left
        # to migrate (as in `test_mid_stream_death_is_typed`)
        monkeypatch.setenv("DL4J_SANDBOX_MODEL", "1")
        w1 = _worker(net, coord.address, dispatch_floor_s=0.02)
        w2 = _worker(net, coord.address, dispatch_floor_s=0.02)
        rset = ReplicaSet(coord.address, "m", refresh_s=0.05)
        router = FleetRouter()
        router.attach_replicas("m", rset)
        try:
            _wait_replicas(rset, 2)
            flood = [router.submit("m", p, 24)
                     for p in list(prompts) * 2]
            time.sleep(0.1)
            w2.stop()           # mid-flood death
            want = generate(net, np.asarray(list(prompts) * 2), 24,
                            temperature=0)
            for s, w_ in zip(flood, want):
                np.testing.assert_array_equal(s.result(120), w_)
            assert any(s.migrations > 0 for s in flood)
            _wait_replicas(rset, 1)
            assert [t for t, _, _ in rset.backends()] == [w1.token]
            # post-kill traffic lands on the survivor
            s = router.submit("m", prompts[0], N_TOK)
            s.result(60)
            assert s.replica == w1.token
        finally:
            rset.close()
            w1.stop()
            w2.stop()

    def test_directory_eviction_migrates_without_deadlock(
            self, coord, net, prompts, monkeypatch):
        """A replica evicted from the serving DIRECTORY while its
        socket still works and streams are in flight: refresh() closes
        the client, whose failing streams migrate SYNCHRONOUSLY on the
        refreshing thread and re-enter refresh()/backends() on the same
        set — a regression to closing under the set lock wedges that
        thread (and every future submit) forever."""
        # the step floor keeps streams in flight at the eviction (see
        # `test_kill_drill_zero_dropped_streams`)
        monkeypatch.setenv("DL4J_SANDBOX_MODEL", "1")
        w1 = _worker(net, coord.address, dispatch_floor_s=0.02)
        w2 = _worker(net, coord.address, dispatch_floor_s=0.02)
        rset = ReplicaSet(coord.address, "m", refresh_s=0.05)
        router = FleetRouter()
        router.attach_replicas("m", rset)
        try:
            _wait_replicas(rset, 2)
            flood = [router.submit("m", p, 24)
                     for p in list(prompts) * 2]
            time.sleep(0.1)
            # vanish from the directory WITHOUT breaking the socket —
            # heartbeats off first, or the beat loop re-registers
            w2._elastic.stop()
            w2._elastic.leave("eviction drill")
            converged = threading.Event()

            def _refresh_until_survivor():
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    rset.refresh(force=True)
                    if [t for t, _, _ in rset.backends()] \
                            == [w1.token]:
                        converged.set()
                        return
                    time.sleep(0.05)

            t = threading.Thread(target=_refresh_until_survivor,
                                 daemon=True)
            t.start()
            assert converged.wait(15), \
                "refresh() wedged evicting a replica with live streams"
            want = generate(net, np.asarray(list(prompts) * 2), 24,
                            temperature=0)
            for s, w_ in zip(flood, want):
                np.testing.assert_array_equal(s.result(120), w_)
            assert any(s.migrations > 0 for s in flood)
        finally:
            rset.close()
            w1.stop()
            w2.stop()

    def test_sampled_migration_keeps_fold_chain(self, coord, net,
                                                prompts):
        rng = np.asarray([7, 29], np.uint32)
        srv = GenerationServer(net, n_slots=2, n_blocks=32, block_len=4)
        srv.start()
        try:
            want = srv.generate_async(prompts[0], 24, temperature=0.8,
                                      rng=rng).result(60)
        finally:
            srv.stop()
        w1 = _worker(net, coord.address)
        w2 = _worker(net, coord.address)
        rset = ReplicaSet(coord.address, "m", refresh_s=0.05)
        router = FleetRouter()
        router.attach_replicas("m", rset)
        try:
            _wait_replicas(rset, 2)
            streams = [router.submit("m", prompts[0], 24,
                                     temperature=0.8, rng=rng)
                       for _ in range(4)]
            time.sleep(0.1)
            w2.stop()
            for s in streams:
                np.testing.assert_array_equal(s.result(120), want)
        finally:
            rset.close()
            w1.stop()
            w2.stop()


# ======================================================== migration
class TestQueuedMigration:
    def test_export_adopt_queued(self, net, prompts, ref):
        """Queued-but-unstarted requests move between servers
        wholesale: same stream object, the adopting server resolves
        it bit-equal."""
        a = GenerationServer(net, n_slots=2, n_blocks=32, block_len=4)
        b = GenerationServer(net, n_slots=4, n_blocks=48, block_len=4)
        a.start()
        # never give a's scheduler a chance: stall it behind a long
        # stream, then export the still-queued tail
        blocker = a.generate_async(prompts[0], 24)
        queued = [a.generate_async(p, N_TOK) for p in prompts[1:4]]
        moved = a.export_queued()
        # at least the tail moves; the blocker moves too if the
        # scheduler hadn't admitted it yet — both are legal
        assert 3 <= len(moved) <= 4
        b.start()
        try:
            assert b.adopt_queued(moved) == len(moved)
            for s, want in zip(queued, ref[1:4]):
                np.testing.assert_array_equal(s.result(60), want)
            blocker.result(60)
            a.drain(timeout=60)
            assert a.open_streams == 0 and b.open_streams == 0
        finally:
            a.stop()
            b.stop()

    def test_swap_migrates_queued_to_successor(self, tmp_path, net,
                                               prompts):
        from deeplearning4j_tpu.serving import FleetServer, ModelRegistry
        net2 = tiny_lm(seed=9)
        reg = ModelRegistry(tmp_path)
        reg.publish("m", net)
        reg.publish("m", net2)
        fleet = FleetServer(reg)
        fleet.deploy("m", version=1, n_slots=2, n_blocks=36,
                     block_len=4)
        srv = fleet.server("m")
        # the incumbent's scheduler is held inside its decode call, as
        # a long compile or a long step holds it, until swap() has
        # exported the tail: the migration is deterministic, not a
        # race. (It used to lean on the first decode program's compile
        # and on the readback after it; a warm compile cache shortens
        # the one, and a loop that launches a step without reading it
        # (ISSUE 30) no longer blocks in the other.)
        exported = threading.Event()
        real_step, real_export = srv.engine.step_ahead, srv.export_queued

        def held_step(*a, **kw):
            out = real_step(*a, **kw)
            if len(srv._slot2req) == 2:      # both admitted, on v1
                exported.wait(30)
            return out

        def export():
            try:
                return real_export()
            finally:
                exported.set()
        srv.engine.step_ahead, srv.export_queued = held_step, export
        inflight = [srv.generate_async(p, N_TOK) for p in prompts[:2]]
        deadline = time.monotonic() + 30
        while srv.queue_depth() and time.monotonic() < deadline:
            time.sleep(0.002)   # both admitted on v1
        queued = [srv.generate_async(p, N_TOK) for p in prompts[2:5]]
        fleet.swap("m", version=2, drain_timeout=120)
        try:
            ref1 = generate(net, prompts[:2], N_TOK, temperature=0)
            ref2 = generate(net2, prompts[2:5], N_TOK, temperature=0)
            # in-flight on v1 finished on v1 (version parity) ...
            for s, want in zip(inflight, ref1):
                np.testing.assert_array_equal(s.result(60), want)
            # ... and the queued tail decoded ENTIRELY on the v2
            # successor
            for s, want in zip(queued, ref2):
                np.testing.assert_array_equal(s.result(60), want)
        finally:
            fleet.stop()


# ============================================== autoscaler: replicas
class _FakeReplica:
    def __init__(self):
        self.stopped = False

    def stop(self):
        self.stopped = True


class TestAutoscalerReplicas:
    def _fleet(self, tmp_path, net):
        from deeplearning4j_tpu.serving import FleetServer, ModelRegistry
        reg = ModelRegistry(tmp_path)
        reg.publish("m", net)
        fleet = FleetServer(reg)
        fleet.deploy("m", n_slots=2, n_blocks=16, block_len=4,
                     max_queue=64)
        return fleet

    def test_grow_replicas_at_vertical_cap(self, tmp_path, net,
                                           prompts):
        from deeplearning4j_tpu.serving import FleetAutoscaler
        fleet = self._fleet(tmp_path, net)
        mgr = ReplicaManager(lambda: _FakeReplica(), min_replicas=1,
                             max_replicas=3)
        mgr.grow()
        scaler = FleetAutoscaler(
            fleet, queue_depth_high=0, max_slots=2, max_blocks=16,
            replicas=mgr)
        try:
            srv = fleet.server("m")
            streams = [srv.generate_async(p, N_TOK) for p in prompts]
            made = scaler.check()
            for s in streams:
                s.result(60)
            grow = [r for r in made
                    if r.get("action") == "grow_replicas"]
            assert grow and mgr.count() == 2
            assert grow[0]["replicas"] == 2
            assert "queue_depth" in grow[0]["reason"]
        finally:
            mgr.stop()
            fleet.stop()

    def test_shrink_after_idle_passes(self, tmp_path, net):
        from deeplearning4j_tpu.serving import FleetAutoscaler
        fleet = self._fleet(tmp_path, net)
        fakes = []

        def factory():
            fakes.append(_FakeReplica())
            return fakes[-1]

        mgr = ReplicaManager(factory, min_replicas=1, max_replicas=3)
        mgr.grow()
        mgr.grow()
        scaler = FleetAutoscaler(fleet, replicas=mgr,
                                 replica_idle_passes=3)
        try:
            made = []
            for _ in range(3):
                made += scaler.check()
            shrink = [r for r in made
                      if r.get("action") == "shrink_replicas"]
            assert shrink and mgr.count() == 1
            assert shrink[0]["replicas"] == 1
            # newest-first: the SECOND fake was released, the first
            # (warmed) replica survives
            assert fakes[1].stopped and not fakes[0].stopped
        finally:
            mgr.stop()
            fleet.stop()

    def test_manager_bounds(self):
        mgr = ReplicaManager(lambda: _FakeReplica(), min_replicas=1,
                             max_replicas=2)
        assert mgr.grow() and mgr.grow() and not mgr.grow()
        assert mgr.count() == 2
        assert mgr.shrink() and not mgr.shrink()
        assert mgr.count() == 1
        with pytest.raises(ValueError):
            ReplicaManager(lambda: None, min_replicas=2, max_replicas=1)


class TestSpawnedChildPlatform:
    """`spawn_replica` children are a CPU drill only when the CALLER
    says so. The child used to get `JAX_PLATFORMS=cpu` by default — on
    a chip host a replica fleet then served from the CPU and said
    nothing. Now it inherits the parent's environment as it is."""

    class _Proc:
        def __init__(self):
            import io
            self.stdout = io.StringIO(
                'REPLICA_READY {"host": "h", "port": 7, "token": "t"}\n')

        def poll(self):
            return None

        def kill(self):
            pass

    def _spawn(self, monkeypatch):
        from deeplearning4j_tpu.serving import replica
        seen = {}

        def popen(cmd, **kw):
            seen.update(cmd=cmd, kw=kw)
            return self._Proc()

        monkeypatch.setattr(replica.subprocess, "Popen", popen)
        proc = replica.spawn_replica("/registry", "m", token="t")
        assert (proc.host, proc.port, proc.token) == ("h", 7, "t")
        return seen

    def test_child_inherits_parent_environment_untouched(self,
                                                         monkeypatch):
        import os
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        seen = self._spawn(monkeypatch)
        # no env override at all: Popen's default is inheritance
        assert seen["kw"].get("env") is None
        assert "JAX_PLATFORMS" not in os.environ     # nor set on the way

    def test_cpu_drill_is_the_callers_choice(self, monkeypatch):
        import os
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        seen = self._spawn(monkeypatch)
        assert seen["kw"].get("env") is None
        assert os.environ["JAX_PLATFORMS"] == "cpu"  # what the child sees
        assert "--registry" in seen["cmd"]
