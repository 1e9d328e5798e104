"""Gang dispatch protocol, single-process: a rank-0 engine publishes
over the REAL TCP wire (engine/gang.py) to a follower engine replaying
in a thread — no jax.distributed, no collectives, so this pins the
protocol layer itself: op framing, codec round-trip, dispatch ordering,
adapter replay, reset, and clean stop. Identical op streams against
identical initial state must produce bit-identical device carries."""

import threading
import time

import jax
import numpy as np
import pytest

from kubeai_tpu.engine.core import Engine, EngineConfig, build_test_engine
from kubeai_tpu.engine.gang import GangFollower, GangPublisher
from kubeai_tpu.engine.sampling import SamplingParams


SECRET = "test-gang-secret"


def connect_pair(pub, timeout=10, secret=SECRET, rank=1):
    """Handshake needs both sides live: connect the follower in a thread
    while the publisher accepts (production runs them as separate
    processes)."""
    out = {}

    def _connect():
        try:
            out["fol"] = GangFollower(
                "127.0.0.1", pub.port, timeout=timeout, secret=secret, rank=rank
            )
        except Exception as e:
            out["err"] = e

    t = threading.Thread(target=_connect, daemon=True)
    t.start()
    pub.accept_all(timeout=timeout)
    t.join(timeout=timeout)
    if "err" in out:
        raise out["err"]
    return out["fol"]


@pytest.fixture()
def pair():
    follower_eng = build_test_engine()
    pub = GangPublisher(1, port=0, host="127.0.0.1", secret=SECRET)
    fol = connect_pair(pub)
    # Leader shares the follower's params/config (same init seed in a
    # real gang; literally shared arrays here).
    leader = Engine(
        follower_eng.model_config,
        follower_eng.params,
        follower_eng.tokenizer,
        EngineConfig(max_slots=4, max_seq_len=256, prefill_buckets=(16, 32, 64, 128)),
        publisher=pub,
    )
    t = threading.Thread(target=follower_eng.run_follower, args=(fol,), daemon=True)
    t.start()
    leader.start()
    yield leader, follower_eng, t
    leader.stop()  # publisher.close() sends "stop"
    t.join(timeout=20)
    assert not t.is_alive(), "follower loop did not exit on stop"


def _sync(get_state, want, timeout=30):
    deadline = time.monotonic() + timeout
    got = None
    while time.monotonic() < deadline:
        try:
            got = np.asarray(jax.device_get(get_state()))
        except RuntimeError:
            # The follower replays with DONATED carries: between a
            # dispatch (input buffer deleted) and the reassignment, a
            # device_get here races into "Array has been deleted" —
            # that's mid-replay, not divergence. Retry until deadline.
            time.sleep(0.05)
            continue
        if np.array_equal(got, want):
            return got
        time.sleep(0.05)
    # Deadline passed: one final fetch so the assertion that follows
    # reports the CURRENT device state, not a stale mid-replay snapshot
    # (or None, if every attempt above raced a donated buffer).
    try:
        return np.asarray(jax.device_get(get_state()))
    except RuntimeError:
        return got


def test_replay_produces_identical_device_state(pair):
    leader, follower, _ = pair
    ids, text, fin = leader.generate(
        list(range(1, 24)), SamplingParams(temperature=0.0, max_tokens=12), timeout=120
    )
    assert fin.completion_tokens >= 1
    # The follower consumed the same prefill + decode stream: its device
    # carries must converge to the leader's exactly.
    want_len = np.asarray(jax.device_get(leader._lengths))
    got_len = _sync(lambda: follower._lengths, want_len)
    np.testing.assert_array_equal(got_len, want_len)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(follower._last_tokens)),
        np.asarray(jax.device_get(leader._last_tokens)),
    )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(follower._keys)),
        np.asarray(jax.device_get(leader._keys)),
    )


def test_embed_and_seeded_sampling_replay(pair):
    leader, follower, _ = pair
    vecs = leader.embed([[1, 2, 3], [9, 8, 7, 6]])
    assert vecs.shape[0] == 2
    ids1, _, _ = leader.generate(
        [5, 6, 7], SamplingParams(temperature=0.9, max_tokens=6, seed=11), timeout=120
    )
    want = np.asarray(jax.device_get(leader._keys))
    got = _sync(lambda: follower._keys, want)
    np.testing.assert_array_equal(got, want)


def test_adapter_ops_replay(pair, tmp_path):
    from tests.test_lora import write_peft_checkpoint

    leader, follower, _ = pair
    write_peft_checkpoint(str(tmp_path / "ad"), leader.model_config, seed=2)
    leader.load_adapter("wire-ad", str(tmp_path / "ad"))
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and follower.loaded_adapters() != ["wire-ad"]:
        time.sleep(0.05)
    assert follower.loaded_adapters() == ["wire-ad"]
    # Adapter-routed generation replays too (bank row identical on both).
    leader.generate(
        [1, 2, 3], SamplingParams(temperature=0.0, max_tokens=4),
        timeout=120, adapter="wire-ad",
    )
    want = np.asarray(jax.device_get(leader._lengths))
    np.testing.assert_array_equal(_sync(lambda: follower._lengths, want), want)

    assert leader.unload_adapter("wire-ad") is True
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and follower.loaded_adapters():
        time.sleep(0.05)
    assert follower.loaded_adapters() == []


def test_reset_op_reinitializes_follower(pair):
    leader, follower, _ = pair
    leader.generate(
        list(range(1, 20)), SamplingParams(temperature=0.0, max_tokens=8), timeout=120
    )
    want = np.asarray(jax.device_get(leader._lengths))
    _sync(lambda: follower._lengths, want)
    assert np.asarray(jax.device_get(follower._lengths)).any()
    # Drain any in-flight publishes, then inject the reset op the leader
    # would broadcast from _recover().
    time.sleep(0.2)
    leader._publisher.publish("reset")
    zeros = np.zeros_like(want)
    np.testing.assert_array_equal(_sync(lambda: follower._lengths, zeros), zeros)


class TestHandshake:
    """Advisor r3 (gang.py): the gang port must not hand the dispatch
    stream (prompt tokens, adapter paths) to any reachable peer, and an
    unauthenticated connection must not consume a follower slot."""

    def test_wrong_secret_rejected_and_real_follower_still_joins(self):
        pub = GangPublisher(1, port=0, host="127.0.0.1", secret=SECRET)
        results = {}

        def imposter():
            try:
                GangFollower(
                    "127.0.0.1", pub.port, timeout=5,
                    secret="wrong-secret", rank=1,
                )
                results["imposter"] = "joined"
            except Exception as e:
                results["imposter"] = e

        t_imp = threading.Thread(target=imposter, daemon=True)
        t_imp.start()
        # The real follower joins AFTER the imposter attempted: the
        # rejected connection must not have consumed the slot.
        fol = connect_pair(pub, timeout=15)
        # The imposter's retry loop runs out its deadline (rejected, it
        # reconnects into the backlog where nothing accepts it).
        t_imp.join(timeout=30)
        assert not t_imp.is_alive(), "imposter attempt did not conclude"
        # The imposter is either rejected by MAC (publisher closes) or
        # fails its own counter-proof check; it never "joins".
        assert results["imposter"] != "joined"
        assert len(pub._ranks) == 1 and 1 in pub._ranks
        fol.close()
        pub.close()

    def test_raw_tcp_connect_gets_no_dispatch_stream(self):
        """A peer that connects but never completes the handshake is
        dropped; publish() reaches only authenticated members."""
        import socket as _socket

        pub = GangPublisher(1, port=0, host="127.0.0.1", secret=SECRET)
        eavesdropper = _socket.create_connection(("127.0.0.1", pub.port), timeout=15)

        def eavesdrop():
            # Receives the challenge once accept_all picks the conn up,
            # then answers with garbage instead of a MAC.
            eavesdropper.recv(16)
            eavesdropper.sendall(b"\x00" * 52)  # rank + nonce + bogus MAC

        t_eve = threading.Thread(target=eavesdrop, daemon=True)
        t_eve.start()
        fol = connect_pair(pub, timeout=15)
        t_eve.join(timeout=10)
        pub.publish("decode", {"x": 1}, {"a": np.arange(3, dtype=np.int32)})
        op, sc, ar = fol.recv()
        assert op == "decode" and sc == {"x": 1}
        # The rejected socket sees EOF (closed by the publisher), not ops.
        eavesdropper.settimeout(5)
        assert eavesdropper.recv(4096) == b""
        eavesdropper.close()
        fol.close()
        pub.close()

    def test_duplicate_rank_rejected(self):
        """The acceptor must reject a correctly-MAC'd connection whose
        rank is already a member (a displacement attack) and out-of-range
        ranks — while still completing the gang with the legit ranks."""
        import socket as _socket
        import struct as _struct

        from kubeai_tpu.engine.gang import _TAG_FOLLOWER, _mac

        pub = GangPublisher(2, port=0, host="127.0.0.1", secret=SECRET)

        def attempt(rank):
            """Hand-rolled follower handshake; returns the publisher's
            32-byte counter-proof, or b'' if the publisher rejected
            (closed) the connection."""
            s = _socket.create_connection(("127.0.0.1", pub.port), timeout=10)
            s.settimeout(10)
            try:
                ch = s.recv(16)
                nonce = b"\x42" * 16
                s.sendall(
                    _struct.pack(">I", rank)
                    + nonce
                    + _mac(SECRET.encode(), _TAG_FOLLOWER, ch + nonce, rank)
                )
                try:
                    return s.recv(32), s
                except OSError:
                    return b"", s
            except OSError:
                return b"", s

        proof1, s1 = attempt(1)
        assert len(proof1) == 32  # first rank-1 join succeeds
        deadline = time.monotonic() + 10
        while 1 not in pub._ranks and time.monotonic() < deadline:
            time.sleep(0.05)
        assert 1 in pub._ranks

        dup_proof, s_dup = attempt(1)  # same rank again: closed, no proof
        assert dup_proof == b""
        bad_proof, s_bad = attempt(7)  # out-of-range rank: closed
        assert bad_proof == b""

        proof2, s2 = attempt(2)  # the gang still completes
        assert len(proof2) == 32
        pub.accept_all(timeout=10)
        assert set(pub._ranks) == {1, 2}
        for s in (s1, s_dup, s_bad, s2):
            s.close()
        pub.close()

    def test_accept_all_times_out(self):
        """accept_all must raise when the gang never assembles — the
        controller relies on the pod failing to recycle a stuck gang."""
        pub = GangPublisher(1, port=0, host="127.0.0.1", secret=SECRET)
        with pytest.raises(TimeoutError):
            pub.accept_all(timeout=1.0)
        pub.close()

    def test_missing_secret_is_an_error(self):
        with pytest.raises(ValueError):
            GangPublisher(1, port=0, host="127.0.0.1", secret="")
        with pytest.raises(ValueError):
            GangFollower("127.0.0.1", 1, timeout=1, secret="", rank=1)


class TestDesyncFatal:
    """Advisor r3 (core.py): after a successful broadcast, a rank-0-only
    dispatch failure means the followers replayed an op rank 0 never
    executed — reset recovery would hang the gang in collectives, so the
    rank must fail in-flight requests and terminate instead."""

    def test_post_broadcast_failure_terminates_rank(self, pair, monkeypatch):
        leader, follower, _ = pair
        calls = {}

        def fake_terminate(message, code):
            calls["msg"] = message
            calls["code"] = code
            leader._fail_inflight(message)
            # Don't _exit (we're pytest); stop the loop like death would.
            leader._running = False

        monkeypatch.setattr(leader, "_terminate_rank", fake_terminate)
        real_decode = leader._decode_jit

        def exploding_decode(*a, **kw):
            raise RuntimeError("simulated rank-0-only dispatch failure")

        # Warm up first so the engine is mid-steady-state.
        leader.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=2), timeout=120)
        monkeypatch.setattr(leader, "_decode_jit", exploding_decode)
        req = leader.submit([4, 5, 6], SamplingParams(temperature=0.0, max_tokens=4))
        deadline = time.monotonic() + 30
        ev = None
        while time.monotonic() < deadline:
            try:
                ev = req.out.get(timeout=5)
            except Exception:
                break
            if ev[0] in ("error", "done"):
                break
        assert ev is not None and ev[0] == "error", f"expected error event, got {ev}"
        assert calls.get("code") == 14, "desync must take the fatal path, not reset recovery"
        monkeypatch.setattr(leader, "_decode_jit", real_decode)

    def test_single_host_failure_still_resets(self):
        """Without a publisher the same failure stays recoverable: reset,
        error in-flight, keep serving."""
        eng = build_test_engine(seed=7)
        eng.start()
        eng.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=2), timeout=120)
        real = eng._decode_jit
        state = {"n": 0}

        def explode_once(*a, **kw):
            if state["n"] == 0:
                state["n"] = 1
                raise RuntimeError("transient device error")
            return real(*a, **kw)

        eng._decode_jit = explode_once
        req = eng.submit([4, 5], SamplingParams(temperature=0.0, max_tokens=3))
        ev = req.out.get(timeout=60)
        assert ev[0] == "error"
        # Engine recovered: a fresh request serves fine.
        ids, _, fin = eng.generate([6, 7], SamplingParams(temperature=0.0, max_tokens=3), timeout=120)
        assert len(ids) == 3
        eng.stop()


class TestAssemblyCountsProvenRanksOnly:
    def test_rolled_back_rank_does_not_complete_assembly(self):
        """Advisor r5: a rank whose counter-proof send fails is rolled
        back — assembly must NOT have counted it, or the gang declares
        itself complete with a permanently missing member whose
        reconnect is then rejected behind the assembled check."""
        import socket as _socket
        import struct as _struct

        from kubeai_tpu.engine.gang import _TAG_FOLLOWER, _mac

        pub = GangPublisher(2, port=0, host="127.0.0.1", secret=SECRET)
        # Deterministically fail rank 1's counter-proof send (a real
        # send to a dead peer can succeed into the kernel buffer, so a
        # socket trick can't pin this race).
        real_send = pub._send_counter_proof
        fail_once = {"armed": True}

        def flaky_send(conn, transcript, rank):
            if rank == 1 and fail_once["armed"]:
                fail_once["armed"] = False
                raise OSError("injected proof-send failure")
            real_send(conn, transcript, rank)

        pub._send_counter_proof = flaky_send

        def half_handshake(rank):
            """Follower that authenticates; the publisher's proof send
            is injected to fail, triggering the rollback path."""
            s = _socket.create_connection(("127.0.0.1", pub.port), timeout=10)
            ch = s.recv(16)
            nonce = b"\x01" * 16
            s.sendall(
                _struct.pack(">I", rank)
                + nonce
                + _mac(SECRET.encode(), _TAG_FOLLOWER, ch + nonce, rank)
            )
            s.close()

        half_handshake(1)
        # Wait for the publisher to register + fail the proof send +
        # roll back.
        deadline = time.time() + 10
        while time.time() < deadline and (
            fail_once["armed"] or 1 in pub._ranks
        ):
            time.sleep(0.05)
        assert 1 not in pub._ranks, "rank 1 was not rolled back"

        # A real rank 2 joins; the gang must NOT assemble on (dead 1, 2).
        out = {}

        def join2():
            try:
                out["fol"] = GangFollower(
                    "127.0.0.1", pub.port, timeout=10, secret=SECRET, rank=2
                )
            except Exception as e:
                out["err"] = e

        t2 = threading.Thread(target=join2, daemon=True)
        t2.start()
        t2.join(timeout=15)
        assert "fol" in out, out.get("err")
        assert not pub._assembled.is_set(), (
            "gang assembled while rank 1 was rolled back"
        )
        # Rank 1 reconnects properly -> NOW the gang completes. (wait,
        # not is_set: the publisher thread sets the event after the
        # follower's handshake returns.)
        fol1 = connect_pair(pub, timeout=15, rank=1)
        assert pub._assembled.wait(5)
        fol1.close()
        out["fol"].close()
        pub.close()


def test_decode_broadcast_carries_arrays_only_and_one_program_replays_it(pair):
    """There is one decode program: the decode broadcast names no flavor
    of it (no scalars at all), carries every array the program's epilogue
    gates branch on, and the follower replays it on the one jitted step
    its own step functions built, compiled once."""
    leader, follower_eng, _ = pair
    pub = leader._publisher
    seen: list[tuple] = []
    real_publish = pub.publish

    def spying_publish(op, scalars=None, arrays=None):
        if op == "decode":
            seen.append((scalars, {k: (v.dtype, v.shape) for k, v in arrays.items()}))
        real_publish(op, scalars, arrays)

    pub.publish = spying_publish
    ids, _, fin = leader.generate(
        list(range(1, 20)), SamplingParams(temperature=0.0, max_tokens=6),
        timeout=120,
    )
    assert fin.completion_tokens >= 1
    assert seen, "no decode op was broadcast"
    for scalars, arrays in seen:
        assert not scalars, scalars
        # The per-slot request parameters the epilogue's gates branch on,
        # want_top among them: a follower that lacked one would run
        # another branch than rank 0.
        assert {"active", "temp", "presence", "freq", "want_top"} <= set(arrays)
        assert arrays["want_top"] == (np.dtype(bool), (4,))
    # The replayed device carries converge to the leader's.
    want = np.asarray(jax.device_get(leader._lengths))
    np.testing.assert_array_equal(_sync(lambda: follower_eng._lengths, want), want)
    assert follower_eng._decode_jit is follower_eng._step_fns.decode_jit
    assert follower_eng._decode_jit._cache_size() == 1


def test_penalized_and_biased_generation_replays(pair):
    """r5 dispatch-key additions (presence/freq/gen_start/bias arrays)
    ride the lockstep stream: a penalized+biased generation must leave
    follower device carries bit-identical to the leader's."""
    leader, follower, _ = pair
    ids, _, fin = leader.generate(
        list(range(1, 20)),
        SamplingParams(
            temperature=0.0, max_tokens=10,
            presence_penalty=1.0, frequency_penalty=1.5,
            logit_bias=((7, -100.0),),
        ),
        timeout=120,
    )
    assert fin.completion_tokens >= 1
    assert 7 not in ids  # bias honored on the leader
    want = np.asarray(jax.device_get(leader._lengths))
    got = _sync(lambda: follower._lengths, want)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(follower._last_tokens)),
        np.asarray(jax.device_get(leader._last_tokens)),
    )
