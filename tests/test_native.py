"""Native core tests.

Mirrors the reference's coverage of its C++ core (SURVEY.md §4: the
controller/fusion/cache logic is exercised indirectly by
test/parallel/*; we test it directly plus cross-check the C++ and
pure-Python implementations agree byte-for-byte on the wire).
"""

import json
import os

import numpy as np
import pytest

from horovod_tpu import native
from horovod_tpu.native import core as ncore
from horovod_tpu.native import fallback, wire


NATIVE = ncore.available()


def make_pair(cls, size=2, fusion=1 << 20, **kw):
    return [cls(r, size, fusion, **kw) for r in range(size)]


def run_cycle(controllers, coordinator=0):
    """One controller cycle: drain -> ingest at coordinator ->
    compute -> apply everywhere. Returns (response_blob, finished_per_rank)."""
    blobs = [c.drain_requests() for c in controllers]
    coord = controllers[coordinator]
    for b in blobs:
        coord.ingest(b)
    resp = coord.compute_responses()
    finished = [c.apply_responses(resp) for c in controllers]
    return resp, finished


CONTROLLER_IMPLS = [fallback.PyController] + (
    [ncore.NativeController] if NATIVE else []
)


@pytest.mark.parametrize("impl", CONTROLLER_IMPLS)
class TestControllerProtocol:
    def test_basic_allreduce_ready(self, impl):
        c0, c1 = make_pair(impl)
        assert c0.enqueue(1, "grad/a", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 4))
        # only rank 0 has submitted -> nothing ready
        resp, fin = run_cycle([c0, c1])
        assert fin == [[], []]
        # now rank 1 submits too -> ready next cycle
        assert c1.enqueue(7, "grad/a", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 4))
        resp, fin = run_cycle([c0, c1])
        rl = wire.parse_response_list(resp)
        assert len(rl.responses) == 1
        assert rl.responses[0].tensor_names == ["grad/a"]
        assert rl.responses[0].tensor_shapes == [(4, 4)]
        assert fin == [[1], [7]]

    def test_duplicate_name_rejected(self, impl):
        (c,) = make_pair(impl, size=1)
        assert c.enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,))
        assert not c.enqueue(2, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,))

    def test_fusion_under_threshold(self, impl):
        # 3 compatible f32 tensors of 10 elements = 40B each; threshold
        # 100B -> first two fuse, third goes alone (greedy, name order).
        c0, c1 = make_pair(impl, fusion=100)
        for c in (c0, c1):
            for i, name in enumerate(["a", "b", "c"]):
                c.enqueue(i + 1, name, wire.ALLREDUCE, wire.RED_SUM, 6, (10,))
        resp, fin = run_cycle([c0, c1])
        rl = wire.parse_response_list(resp)
        assert [r.tensor_names for r in rl.responses] == [["a", "b"], ["c"]]
        assert rl.responses[0].total_bytes == 80
        # finished seqs preserve response order
        assert fin[0] == [1, 2, 3]

    def test_no_fusion_across_dtype_or_op(self, impl):
        c0, c1 = make_pair(impl, fusion=1 << 20)
        for c in (c0, c1):
            c.enqueue(1, "a", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
            c.enqueue(2, "b", wire.ALLREDUCE, wire.RED_SUM, 5, (4,))  # bf16
            c.enqueue(3, "c", wire.BROADCAST, wire.RED_SUM, 6, (4,), 0, -1, 0)
        resp, _ = run_cycle([c0, c1])
        rl = wire.parse_response_list(resp)
        assert len(rl.responses) == 3

    def test_deterministic_name_order(self, impl):
        # Ranks enqueue in different orders; response order is sorted
        # by name regardless (parity: FuseResponses determinism).
        c0, c1 = make_pair(impl)
        for name, seq in (("z", 1), ("a", 2), ("m", 3)):
            c0.enqueue(seq, name, wire.ALLREDUCE, wire.RED_SUM, 6, (1000,))
        for name, seq in (("m", 1), ("z", 2), ("a", 3)):
            c1.enqueue(seq, name, wire.ALLREDUCE, wire.RED_SUM, 6, (1000,))
        resp, _ = run_cycle([c0, c1], coordinator=0)
        rl = wire.parse_response_list(resp)
        names = [n for r in rl.responses for n in r.tensor_names]
        assert names == ["a", "m", "z"]

    def test_response_cache_steady_state(self, impl):
        c0, c1 = make_pair(impl)
        for step in range(3):
            for seq, c in enumerate((c0, c1), start=step * 10):
                c.enqueue(seq + 1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (8,))
            blobs = [c.drain_requests() for c in (c0, c1)]
            parsed = [wire.parse_request_list(b) for b in blobs]
            if step == 0:
                assert not parsed[0].cache_bypass
                assert not parsed[0].requests[0].cached
            else:
                # steady state: the whole drain rides the cache-bit
                # vector (no serialized requests at all)
                for p in parsed:
                    assert p.cache_bypass
                    assert p.requests == []
                    assert wire.words_to_bits(p.cache_bits) == [0]
            for b in blobs:
                c0.ingest(b)
            resp = c0.compute_responses()
            for c in (c0, c1):
                c.apply_responses(resp)
            rl = wire.parse_response_list(resp)
            assert [r.tensor_names for r in rl.responses] == [["g"]]
            # cached expansion must preserve shape metadata
            assert rl.responses[0].tensor_shapes == [(8,)]
        assert c0.cache_size == 1

    def test_cache_eviction_consistency(self, impl):
        c0, c1 = make_pair(impl, cache_capacity=2)
        # insert 3 distinct signatures -> evicts the LRU; both ranks
        # must still agree (we just check no wrong-tensor responses).
        for step, name in enumerate(["a", "b", "c", "a", "b", "c"]):
            for c in (c0, c1):
                c.enqueue(step * 2 + c.rank + 1, name,
                          wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
            resp, fin = run_cycle([c0, c1])
            rl = wire.parse_response_list(resp)
            assert [r.tensor_names for r in rl.responses] == [[name]]
        assert c0.cache_size == 2

    def test_group_gating(self, impl):
        c0, c1 = make_pair(impl)
        for c in (c0, c1):
            c.declare_group(5, 2)
        # both ranks ready on only one of two group members -> held back
        for c in (c0, c1):
            c.enqueue(1, "g/x", wire.ALLREDUCE, wire.RED_SUM, 6, (4,), 0, 5)
        resp, fin = run_cycle([c0, c1])
        assert wire.parse_response_list(resp).responses == []
        # second member arrives -> both released together
        for c in (c0, c1):
            c.enqueue(2, "g/y", wire.ALLREDUCE, wire.RED_SUM, 6, (4,), 0, 5)
        resp, fin = run_cycle([c0, c1])
        rl = wire.parse_response_list(resp)
        names = [n for r in rl.responses for n in r.tensor_names]
        assert sorted(names) == ["g/x", "g/y"]

    def test_join(self, impl):
        c0, c1 = make_pair(impl)
        c0.set_joined()
        resp, _ = run_cycle([c0, c1])
        assert wire.parse_response_list(resp).join_last_rank == -1
        c1.set_joined()
        resp, _ = run_cycle([c0, c1])
        assert wire.parse_response_list(resp).join_last_rank == 1

    def test_process_set_subset(self, impl):
        c0, c1, c2 = make_pair(impl, size=3)
        for c in (c0, c1, c2):
            c.register_process_set(7, [0, 2])
        # only the two members of process set 7 need to report
        c0.enqueue(1, "ps", wire.ALLREDUCE, wire.RED_SUM, 6, (4,), 7)
        c2.enqueue(1, "ps", wire.ALLREDUCE, wire.RED_SUM, 6, (4,), 7)
        resp, fin = run_cycle([c0, c1, c2])
        rl = wire.parse_response_list(resp)
        assert [r.tensor_names for r in rl.responses] == [["ps"]]
        assert rl.responses[0].process_set_id == 7
        assert fin[0] == [1] and fin[1] == [] and fin[2] == [1]

    def test_stall_detection(self, impl):
        c0, c1 = make_pair(impl, stall_warn_s=0.0)
        c0.enqueue(1, "stuck", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        run_cycle([c0, c1])
        stalls = c0.check_stalls()
        assert len(stalls) == 1
        assert stalls[0]["name"] == "stuck"
        assert stalls[0]["present"] == [0]
        assert stalls[0]["missing"] == [1]

    def test_pending_introspection(self, impl):
        (c,) = make_pair(impl, size=1)
        c.enqueue(1, "t", wire.ALLREDUCE, wire.RED_SUM, 6, (10,))
        assert c.pending_count == 1
        assert c.pending_bytes == 40
        c.drain_requests()
        assert c.pending_count == 0

    def test_group_fusion_merges_non_adjacent(self, impl):
        """Compatibility-group fusion: an incompatible response landing
        between two compatible ones (table-key order a < m < z) must
        not split their fusion group."""
        c0, c1 = make_pair(impl, fusion=1 << 20)
        for c in (c0, c1):
            c.enqueue(1, "a", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
            c.enqueue(2, "m", wire.ALLREDUCE, wire.RED_SUM, 5, (4,))  # bf16
            c.enqueue(3, "z", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        resp, _ = run_cycle([c0, c1])
        rl = wire.parse_response_list(resp)
        assert [r.tensor_names for r in rl.responses] == [["a", "z"], ["m"]]

    def test_predict_responses_matches_coordinator(self, impl):
        """Steady-state schedule prediction: predict_responses(bits)
        must be byte-identical to what the coordinator computes for
        the same bypass cycle, None for unknown bits; finish() retires
        in-flight names so re-enqueues pass the duplicate guard."""
        c0, c1 = make_pair(impl, fusion=1 << 20)
        for step in range(2):
            for c in (c0, c1):
                c.enqueue(step * 4 + 1, "p/a", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (8,))
                c.enqueue(step * 4 + 2, "p/b", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (8,))
            run_cycle([c0, c1])
        # third (steady) cycle: predict BEFORE the exchange, then run
        # the real negotiation and compare bytes
        for c in (c0, c1):
            c.enqueue(90 + c.rank, "p/a", wire.ALLREDUCE, wire.RED_SUM,
                      6, (8,))
            c.enqueue(95 + c.rank, "p/b", wire.ALLREDUCE, wire.RED_SUM,
                      6, (8,))
        predicted = c1.predict_responses([0, 1])
        assert predicted is not None
        blobs = [c.drain_requests() for c in (c0, c1)]
        assert wire.parse_request_list(blobs[0]).cache_bypass
        for b in blobs:
            c0.ingest(b)
        real = c0.compute_responses()
        assert predicted == real
        rl = wire.parse_response_list(predicted)
        assert [r.tensor_names for r in rl.responses] == [["p/a", "p/b"]]
        assert rl.responses[0].tensor_shapes == [(8,), (8,)]
        # unknown bit: no prediction
        assert c0.predict_responses([0, 9]) is None
        # finish() retires rank 1's in-flight entries eagerly
        assert sorted(c1.finish(["p/a", "p/b"])) == [91, 96]
        assert c1.enqueue(200, "p/a", wire.ALLREDUCE, wire.RED_SUM,
                          6, (8,))

    def test_bypass_streak_and_periodic_resync(self, impl):
        """Steady state cycles between bypass blobs and the periodic
        full resync: with resync_every=4 the cadence is miss, 3×
        bypass, resync, 3× bypass, resync, ..."""
        c0, c1 = make_pair(impl, resync_every=4)
        for step in range(9):
            for c in (c0, c1):
                c.enqueue(step * 2 + c.rank + 1, "g",
                          wire.ALLREDUCE, wire.RED_SUM, 6, (8,))
            blobs = [c.drain_requests() for c in (c0, c1)]
            parsed = wire.parse_request_list(blobs[0])
            if step == 0:
                assert not parsed.cache_bypass and not parsed.cache_resync
                assert not parsed.requests[0].cached
            elif step % 4 == 0:
                # periodic resync: FULL entries (not bit-compressed),
                # flagged, hits still counted for the metrics frame
                assert parsed.cache_resync and not parsed.cache_bypass
                assert not parsed.requests[0].cached
                assert parsed.requests[0].entry.shape == (8,)
                assert parsed.cache_hits == [0]
            else:
                assert parsed.cache_bypass and not parsed.cache_resync
                assert parsed.requests == []
                assert wire.words_to_bits(parsed.cache_bits) == [0]
            for b in blobs:
                c0.ingest(b)
            resp = c0.compute_responses()
            fins = [c.apply_responses(resp) for c in (c0, c1)]
            rl = wire.parse_response_list(resp)
            assert [r.tensor_names for r in rl.responses] == [["g"]]
            assert rl.responses[0].tensor_shapes == [(8,)]
            assert fins[0] == [step * 2 + 1]

    def test_miss_exits_bypass_and_rejoins(self, impl):
        """A novel tensor mid-steady-state (miss) drops the cycle back
        to the full wire (hits bit-compressed, the miss full), then the
        next all-hit cycle resumes bypassing."""
        c0, c1 = make_pair(impl)
        seq = iter(range(1, 100))

        def cycle(names):
            for c in (c0, c1):
                for nm in names:
                    c.enqueue(next(seq), nm, wire.ALLREDUCE,
                              wire.RED_SUM, 6, (4,))
            blobs = [c.drain_requests() for c in (c0, c1)]
            for b in blobs:
                c0.ingest(b)
            resp = c0.compute_responses()
            for c in (c0, c1):
                c.apply_responses(resp)
            return wire.parse_request_list(blobs[0])

        cycle(["a"])                    # miss: full
        assert cycle(["a"]).cache_bypass        # steady state
        mixed = cycle(["a", "b"])       # miss on b: full cycle
        assert not mixed.cache_bypass
        assert mixed.requests[0].cached          # a rides its bit
        assert not mixed.requests[1].cached      # b full
        rejoin = cycle(["a", "b"])      # both hit again
        assert rejoin.cache_bypass
        assert wire.words_to_bits(rejoin.cache_bits) == [0, 1]

    def test_membership_change_forces_full_cycle(self, impl):
        c0, c1 = make_pair(impl)
        for c in (c0, c1):
            c.enqueue(1, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        run_cycle([c0, c1])
        c0.set_joined()
        c0.enqueue(2, "g", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        parsed = wire.parse_request_list(c0.drain_requests())
        # a joined/shutdown announcement must never ride a bypass blob
        assert parsed.joined and not parsed.cache_bypass

    def test_unknown_bypass_bit_requests_resync(self, impl):
        """Cache divergence recovery: an unexpandable bypass bit makes
        the coordinator broadcast cache_resync_needed (one-shot), and a
        rank applying it re-announces its in-flight ops as full
        entries so the op completes."""
        c0, c1 = make_pair(impl)
        # rank 1's op goes in flight (drained, never answered)
        c1.enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        c0.ingest(c0.drain_requests())
        c0.ingest(c1.drain_requests())
        resp = c0.compute_responses()
        for c in (c0, c1):
            c.apply_responses(resp)
        # corrupt scenario: a bypass blob referencing a bit the
        # coordinator never created
        rogue = wire.RequestList(rank=1, cache_bypass=True,
                                 cache_bits=wire.bits_to_words([7]))
        c0.ingest(wire.serialize_request_list(rogue))
        rl = wire.parse_response_list(c0.compute_responses())
        assert rl.cache_resync_needed
        # one-shot: the next ResponseList is clean
        rl2 = wire.parse_response_list(c0.compute_responses())
        assert not rl2.cache_resync_needed
        # a rank that applies the resync response re-announces its
        # in-flight op with the FULL entry
        c1.apply_responses(wire.serialize_response_list(
            wire.ResponseList(cache_resync_needed=True)))
        parsed = wire.parse_request_list(c1.drain_requests())
        assert parsed.cache_resync
        assert [rq.entry.name for rq in parsed.requests] == ["x"]
        assert not parsed.requests[0].cached
        assert parsed.requests[0].entry.shape == (4,)
        # the re-announcement completes the op once rank 0 reports too
        c0.enqueue(2, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (4,))
        resp, fins = run_cycle([c0, c1])
        names = [n for r in wire.parse_response_list(resp).responses
                 for n in r.tensor_names]
        assert names == ["x"]
        assert fins[1] == [1]


class TestCacheBitsWire:
    """The v3 cache_bits frame: packing helpers + blob round-trips."""

    def test_bits_words_roundtrip(self):
        for bits in ([], [0], [63], [64], [0, 1, 63, 64, 65, 127, 128],
                     [5, 200, 1023]):
            words = wire.bits_to_words(bits)
            assert wire.words_to_bits(words) == sorted(bits)

    def test_request_list_bypass_roundtrip(self):
        rl = wire.RequestList(rank=3, cache_bypass=True,
                              cache_bits=wire.bits_to_words([0, 2, 70]))
        out = wire.parse_request_list(wire.serialize_request_list(rl))
        assert out.rank == 3
        assert out.cache_bypass and not out.cache_resync
        assert out.requests == []
        assert wire.words_to_bits(out.cache_bits) == [0, 2, 70]

    def test_request_list_resync_flag_roundtrip(self):
        rl = wire.RequestList(rank=1, cache_resync=True)
        out = wire.parse_request_list(wire.serialize_request_list(rl))
        assert out.cache_resync and not out.cache_bypass

    def test_response_list_resync_needed_roundtrip(self):
        rl = wire.ResponseList(cache_resync_needed=True)
        out = wire.parse_response_list(wire.serialize_response_list(rl))
        assert out.cache_resync_needed

    def test_bypass_blob_is_much_smaller(self):
        """The point of the fast path: a steady-state drain of many ops
        is a handful of bytes, not O(requests)."""
        full = wire.RequestList(rank=0)
        for i in range(32):
            full.requests.append(wire.Request(rank=0, entry=wire.Entry(
                seq=i, name=f"grad/layer{i}/kernel", dtype=6,
                shape=(128, 128))))
        bypass = wire.RequestList(
            rank=0, cache_bypass=True,
            cache_bits=wire.bits_to_words(list(range(32))))
        nfull = len(wire.serialize_request_list(full))
        nbyp = len(wire.serialize_request_list(bypass))
        assert nbyp < nfull / 20
        assert nbyp < 48  # v5: +8 bytes of burst-unit delimiter


@pytest.mark.skipif(not NATIVE, reason="no C++ toolchain")
class TestNativePythonAgreement:
    """The native and Python controllers must emit identical bytes for
    identical inputs — that is what allows mixed fleets."""

    def test_wire_bytes_identical(self):
        seq_ops = [
            (1, "w/dense/kernel", wire.ALLREDUCE, wire.RED_AVERAGE, 6, (128, 64)),
            (2, "w/dense/bias", wire.ALLREDUCE, wire.RED_AVERAGE, 6, (64,)),
            (3, "bcast/step", wire.BROADCAST, wire.RED_SUM, 3, ()),
        ]
        for step in range(3):  # includes cache steady-state cycles
            nat = make_pair(ncore.NativeController, size=2, fusion=1 << 10)
            py = make_pair(fallback.PyController, size=2, fusion=1 << 10)
            for _ in range(step + 1):
                for c in nat + py:
                    for seq, name, op, red, dt, shape in seq_ops:
                        c.enqueue(seq, name, op, red, dt, shape,
                                  0, -1, 0 if op == wire.BROADCAST else -1)
                nat_blobs = [c.drain_requests() for c in nat]
                py_blobs = [c.drain_requests() for c in py]
                assert nat_blobs == py_blobs
                for b in nat_blobs:
                    nat[0].ingest(b)
                for b in py_blobs:
                    py[0].ingest(b)
                nat_resp = nat[0].compute_responses()
                py_resp = py[0].compute_responses()
                assert nat_resp == py_resp
                nat_fins = [c.apply_responses(nat_resp) for c in nat]
                py_fins = [c.apply_responses(py_resp) for c in py]
                assert nat_fins == py_fins

    def test_predicted_confirmation_bytes_identical(self):
        """v5 acceptance: a fully-predicted steady cycle — every rank
        posts a `predicted` bypass confirmation — suppresses to a
        confirm hash instead of a response stream, and the native and
        Python coordinators must emit byte-identical ResponseLists
        whose hash equals the FNV-1a64 of the predicted bytes."""
        nat = make_pair(ncore.NativeController, size=2, fusion=1 << 20)
        py = make_pair(fallback.PyController, size=2, fusion=1 << 20)

        def cycle(pairs, seq0):
            for pair in pairs:
                for c in pair:
                    c.enqueue(seq0 + c.rank, "pc/a", wire.ALLREDUCE,
                              wire.RED_SUM, 6, (8,))
                    c.enqueue(seq0 + 10 + c.rank, "pc/b", wire.ALLREDUCE,
                              wire.RED_SUM, 6, (8,))
            nat_blobs = [c.drain_requests() for c in pairs[0]]
            py_blobs = [c.drain_requests() for c in pairs[1]]
            assert nat_blobs == py_blobs
            return nat_blobs, py_blobs

        # two warm-up cycles establish the cache; cycle 3 is steady
        for step in range(2):
            nat_blobs, py_blobs = cycle((nat, py), step * 100 + 1)
            for b in nat_blobs:
                nat[0].ingest(b)
            for b in py_blobs:
                py[0].ingest(b)
            nat_resp = nat[0].compute_responses()
            py_resp = py[0].compute_responses()
            assert nat_resp == py_resp
            for c in nat:
                c.apply_responses(nat_resp)
            for c in py:
                c.apply_responses(py_resp)

        # steady cycle: every rank predicts locally, then posts its
        # drained bypass blob with the predicted flag set (the compact
        # post-hoc confirmation) instead of waiting for responses
        for pair in (nat, py):
            for c in pair:
                c.enqueue(300 + c.rank, "pc/a", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (8,))
                c.enqueue(310 + c.rank, "pc/b", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (8,))
        nat_pred = [c.predict_responses([0, 1]) for c in nat]
        py_pred = [c.predict_responses([0, 1]) for c in py]
        assert nat_pred[0] is not None
        assert nat_pred == py_pred
        nat_blobs = [wire.mark_predicted(c.drain_requests()) for c in nat]
        py_blobs = [wire.mark_predicted(c.drain_requests()) for c in py]
        assert nat_blobs == py_blobs
        parsed = wire.parse_request_list(py_blobs[0])
        assert parsed.predicted and parsed.cache_bypass
        assert parsed.burst_len == 2 and parsed.burst_id > 0
        for b in nat_blobs:
            nat[0].ingest(b)
        for b in py_blobs:
            py[0].ingest(b)
        nat_resp = nat[0].compute_responses()
        py_resp = py[0].compute_responses()
        assert nat_resp == py_resp
        rl = wire.parse_response_list(py_resp)
        assert rl.responses == []  # suppressed: nobody needs the bytes
        assert rl.confirm_hashes == [wire.fnv1a64(py_pred[0])]
        # force_resync (mispredict re-anchor) agrees byte-for-byte too:
        # the next drain is a full-entry resync frame in both impls
        for pair in (nat, py):
            for c in pair:
                c.force_resync()
                c.enqueue(400 + c.rank, "pc/a", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (8,))
        nat_blobs = [c.drain_requests() for c in nat]
        py_blobs = [c.drain_requests() for c in py]
        assert nat_blobs == py_blobs
        parsed = wire.parse_request_list(py_blobs[0])
        assert parsed.cache_resync and not parsed.cache_bypass
        assert parsed.requests[0].entry.shape == (8,)

    def test_join_semantics_bytes_identical(self):
        """Joined-rank implicit readiness, the per-set table keys, and
        the joined-zero-contribution error texts must agree between the
        C++ and Python controllers byte-for-byte."""
        nat = make_pair(ncore.NativeController, size=2, fusion=1 << 10)
        py = make_pair(fallback.PyController, size=2, fusion=1 << 10)
        for pair in (nat, py):
            pair[1].set_joined()
            # rank 0 alone: sum unlocks via implicit readiness, min and
            # int8 produce error responses, broadcast from joined root 1
            # errors too.
            pair[0].enqueue(1, "ok_sum", wire.ALLREDUCE, wire.RED_SUM,
                            6, (4,))
            pair[0].enqueue(2, "bad_min", wire.ALLREDUCE, wire.RED_MIN,
                            6, (4,))
            pair[0].enqueue(3, "bad_int8", wire.ALLREDUCE, wire.RED_SUM,
                            1, (4,))
            pair[0].enqueue(4, "bad_root", wire.BROADCAST, wire.RED_SUM,
                            6, (4,), 0, -1, 1)
        nat_blobs = [c.drain_requests() for c in nat]
        py_blobs = [c.drain_requests() for c in py]
        assert nat_blobs == py_blobs
        for b in nat_blobs:
            nat[0].ingest(b)
        for b in py_blobs:
            py[0].ingest(b)
        nat_resp = nat[0].compute_responses()
        py_resp = py[0].compute_responses()
        assert nat_resp == py_resp
        rl = wire.parse_response_list(py_resp)
        by_name = {rs.tensor_names[0]: rs for rs in rl.responses}
        assert by_name["ok_sum"].error == ""
        assert "does not support joined-rank" in by_name["bad_min"].error
        assert "int8 wire format" in by_name["bad_int8"].error
        assert by_name["bad_root"].error == "broadcast root rank 1 has joined"

    def test_per_process_set_table_keys_bytes_identical(self):
        """Same tensor name in two disjoint sets -> two responses; C++
        and Python must order and serialize them identically."""
        nat = make_pair(ncore.NativeController, size=4, fusion=1 << 10)
        py = make_pair(fallback.PyController, size=4, fusion=1 << 10)
        for pair in (nat, py):
            for c in pair:
                c.register_process_set(1, [0, 2])
                c.register_process_set(2, [1, 3])
            pair[0].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,), 1)
            pair[2].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2,), 1)
            pair[1].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (5,), 2)
            pair[3].enqueue(1, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (5,), 2)
        nat_blobs = [c.drain_requests() for c in nat]
        py_blobs = [c.drain_requests() for c in py]
        assert nat_blobs == py_blobs
        for b in nat_blobs:
            nat[0].ingest(b)
        for b in py_blobs:
            py[0].ingest(b)
        nat_resp = nat[0].compute_responses()
        py_resp = py[0].compute_responses()
        assert nat_resp == py_resp
        rl = wire.parse_response_list(py_resp)
        assert len(rl.responses) == 2
        assert sorted(rs.process_set_id for rs in rl.responses) == [1, 2]

    def test_shutdown_semantics_bytes_identical(self):
        """Coordinated shutdown: global quiesce flag only when EVERY
        rank announced; pending tensors requiring an announced rank
        fail promptly with identical error bytes in both impls."""
        nat = make_pair(ncore.NativeController, size=2, fusion=1 << 10)
        py = make_pair(fallback.PyController, size=2, fusion=1 << 10)
        for pair in (nat, py):
            pair[0].enqueue(1, "stranded", wire.ALLREDUCE, wire.RED_SUM,
                            6, (4,))
            pair[1].set_shutdown()
        nat_blobs = [c.drain_requests() for c in nat]
        py_blobs = [c.drain_requests() for c in py]
        assert nat_blobs == py_blobs
        for b in nat_blobs:
            nat[0].ingest(b)
        for b in py_blobs:
            py[0].ingest(b)
        nat_resp = nat[0].compute_responses()
        py_resp = py[0].compute_responses()
        assert nat_resp == py_resp
        rl = wire.parse_response_list(py_resp)
        assert not rl.shutdown  # only rank 1 announced
        assert len(rl.responses) == 1
        assert rl.responses[0].error == "rank 1 has shut down"
        # rank 0 announces too -> global quiesce
        for pair in (nat, py):
            pair[0].set_shutdown()
        nat_blobs = [c.drain_requests() for c in nat]
        py_blobs = [c.drain_requests() for c in py]
        for b in nat_blobs:
            nat[0].ingest(b)
        for b in py_blobs:
            py[0].ingest(b)
        nat_resp = nat[0].compute_responses()
        py_resp = py[0].compute_responses()
        assert nat_resp == py_resp
        assert wire.parse_response_list(py_resp).shutdown

    def test_bypass_and_resync_cycle_bytes_identical(self):
        """The v3 steady-state protocol — bypass bit-vector blobs, the
        periodic full-resync cadence, and the responses they produce —
        must agree byte-for-byte between the C++ and Python
        controllers across enough cycles to cover every phase."""
        nat = make_pair(ncore.NativeController, size=2, fusion=1 << 10,
                        resync_every=3)
        py = make_pair(fallback.PyController, size=2, fusion=1 << 10,
                       resync_every=3)
        saw_bypass = saw_resync = False
        for step in range(8):
            for pair in (nat, py):
                for c in pair:
                    c.enqueue(step * 10 + c.rank + 1, "w/kernel",
                              wire.ALLREDUCE, wire.RED_AVERAGE, 6,
                              (64, 64))
                    c.enqueue(step * 10 + c.rank + 5, "w/bias",
                              wire.ALLREDUCE, wire.RED_AVERAGE, 6,
                              (64,))
            nat_blobs = [c.drain_requests() for c in nat]
            py_blobs = [c.drain_requests() for c in py]
            assert nat_blobs == py_blobs, f"step {step}"
            parsed = wire.parse_request_list(py_blobs[0])
            saw_bypass |= parsed.cache_bypass
            saw_resync |= parsed.cache_resync
            for b in nat_blobs:
                nat[0].ingest(b)
            for b in py_blobs:
                py[0].ingest(b)
            nat_resp = nat[0].compute_responses()
            py_resp = py[0].compute_responses()
            assert nat_resp == py_resp, f"step {step}"
            nat_fins = [c.apply_responses(nat_resp) for c in nat]
            py_fins = [c.apply_responses(py_resp) for c in py]
            assert nat_fins == py_fins
        assert saw_bypass and saw_resync

    def test_resync_needed_recovery_bytes_identical(self):
        """The unknown-bit -> cache_resync_needed -> in-flight
        re-announcement path produces identical bytes in both impls."""
        rogue = wire.serialize_request_list(wire.RequestList(
            rank=1, cache_bypass=True,
            cache_bits=wire.bits_to_words([9])))
        force = wire.serialize_response_list(wire.ResponseList(
            cache_resync_needed=True))
        outs = []
        for cls in (ncore.NativeController, fallback.PyController):
            c0, c1 = make_pair(cls, size=2)
            c1.enqueue(4, "x", wire.ALLREDUCE, wire.RED_SUM, 6, (2, 3))
            c1.drain_requests()          # x now in flight at rank 1
            c0.ingest(rogue)
            resp = c0.compute_responses()
            c1.apply_responses(force)
            reann = c1.drain_requests()
            outs.append((resp, reann))
        assert outs[0] == outs[1]
        assert wire.parse_response_list(outs[0][0]).cache_resync_needed
        parsed = wire.parse_request_list(outs[0][1])
        assert parsed.cache_resync
        assert [rq.entry.name for rq in parsed.requests] == ["x"]

    def test_mismatch_diagnostics_bytes_identical(self):
        """Cross-rank mismatch error responses (named-rank diagnostics
        + forced cache resync) must serialize identically from the C++
        and Python controllers — including after a bypass cycle where
        one rank's bit expands against another rank's conflicting full
        entry."""
        outs = []
        for cls in (ncore.NativeController, fallback.PyController):
            c0, c1 = make_pair(cls, size=2)
            c0.enqueue(1, "w/k", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 4))
            c1.enqueue(1, "w/k", wire.ALLREDUCE, wire.RED_SUM, 6, (4, 8))
            resp, _fin = run_cycle([c0, c1])
            # dtype mismatch on a broadcast with disagreeing roots too
            c0.enqueue(2, "b", wire.BROADCAST, wire.RED_SUM, 6, (2,),
                       0, -1, 0)
            c1.enqueue(2, "b", wire.BROADCAST, wire.RED_SUM, 3, (2,),
                       0, -1, 1)
            resp2, _fin = run_cycle([c0, c1])
            outs.append((resp, resp2))
        assert outs[0] == outs[1]
        rl = wire.parse_response_list(outs[0][0])
        assert rl.cache_resync_needed
        assert len(rl.responses) == 1
        err = rl.responses[0].error
        assert err.startswith("cross-rank tensor mismatch for 'w/k'")
        assert "rank 0 submitted op=0 red_op=0 dtype=6 shape=[4,4]" in err
        assert "rank 1 submitted op=0 red_op=0 dtype=6 shape=[4,8]" in err
        rl2 = wire.parse_response_list(outs[0][1])
        err2 = rl2.responses[0].error
        assert "dtype=6" in err2 and "dtype=3" in err2
        assert "root_rank=0" in err2 and "root_rank=1" in err2

    def test_cross_impl_fleet(self):
        """Rank 0 native + rank 1 Python coordinate successfully."""
        c0 = ncore.NativeController(0, 2, 1 << 20)
        c1 = fallback.PyController(1, 2, 1 << 20)
        for step in range(2):
            for c in (c0, c1):
                c.enqueue(step + 1, "mixed", wire.ALLREDUCE,
                          wire.RED_SUM, 6, (16,))
            resp, fin = run_cycle([c0, c1])
            assert fin == [[step + 1], [step + 1]]


@pytest.mark.skipif(not NATIVE, reason="no C++ toolchain")
class TestNativeUtilities:
    def test_parallel_gather_scatter(self):
        import numpy as np

        srcs = [np.arange(i * 7, i * 7 + 13, dtype=np.uint8) for i in range(5)]
        total = sum(s.nbytes for s in srcs)
        dst = bytearray(total)
        ncore.parallel_gather(memoryview(dst),
                              [memoryview(s) for s in srcs])
        assert bytes(dst) == b"".join(s.tobytes() for s in srcs)
        outs = [bytearray(13) for _ in range(5)]
        ncore.parallel_scatter(memoryview(bytes(dst)),
                               [memoryview(o) for o in outs])
        for s, o in zip(srcs, outs):
            assert bytes(o) == s.tobytes()

    def test_timeline_writer(self, tmp_path):
        p = tmp_path / "tl.json"
        tl = ncore.NativeTimeline(str(p), rank=0)
        tl.event("NEGOTIATE_ALLREDUCE", "B", "negotiate", 1.0)
        tl.event("NEGOTIATE_ALLREDUCE", "E", "negotiate", 2.0)
        tl.event("XLA_COLLECTIVE", "X", "comm", 3.0, 4.5)
        tl.mark_cycle(10.0)
        tl.close()
        events = json.loads(p.read_text())
        assert [e["ph"] for e in events] == ["B", "E", "X", "i"]
        assert events[2]["dur"] == 4.5

    def test_pool_threads(self):
        lib = ncore.load()
        assert lib.hvt_pool_num_threads() >= 1


def test_make_controller_fallback_env(monkeypatch):
    monkeypatch.setenv("HVTPU_FORCE_PY_CONTROLLER", "1")
    c = native.make_controller(0, 1, 1 << 20)
    assert isinstance(c, fallback.PyController)


class TestNativeGaussianProcess:
    """native/src/gaussian_process.cc vs the numpy executable-spec twin
    (obs/gaussian_process.py) — parity: gaussian_process.cc +
    bayesian_optimization.cc keeping the GP math native."""

    def _data(self, n=15, d=2, seed=3):
        rng = np.random.RandomState(seed)
        xs = rng.rand(n, d)
        ys = np.sin(3 * xs[:, 0]) * np.cos(2 * xs[:, 1]) + 0.05 * rng.randn(n)
        cand = rng.rand(64, d)
        return xs, ys, cand

    def test_predict_matches_numpy_twin(self, monkeypatch):
        if not ncore.available():
            pytest.skip("no native toolchain")
        from horovod_tpu.obs import gaussian_process as gpmod

        xs, ys, cand = self._data()
        out = ncore.gp_predict(xs, ys, cand, length_scale=0.3,
                               noise=1e-4, signal_variance=1.0)
        assert out is not None
        mu_n, sig_n = out
        gp = gpmod.GaussianProcess(length_scale=0.3, noise=1e-4)
        gp.fit(xs, ys)
        monkeypatch.setenv("HVTPU_FORCE_PY_GP", "1")  # numpy twin
        mu_p, sig_p = gp.predict(cand)
        np.testing.assert_allclose(mu_n, mu_p, atol=1e-10)
        np.testing.assert_allclose(sig_n, sig_p, atol=1e-10)

    def test_ei_matches_numpy_twin(self, monkeypatch):
        if not ncore.available():
            pytest.skip("no native toolchain")
        from horovod_tpu.obs import gaussian_process as gpmod

        xs, ys, cand = self._data(seed=7)
        ei_n = ncore.gp_expected_improvement(
            xs, ys, cand, length_scale=0.3, noise=1e-4,
            signal_variance=1.0, best_y=float(ys.max()), xi=0.01,
        )
        assert ei_n is not None
        gp = gpmod.GaussianProcess(length_scale=0.3, noise=1e-4)
        gp.fit(xs, ys)
        monkeypatch.setenv("HVTPU_FORCE_PY_GP", "1")
        ei_p = gpmod.expected_improvement(gp, cand, float(ys.max()))
        np.testing.assert_allclose(ei_n, ei_p, atol=1e-10)

    def test_gp_predict_routes_native_by_default(self, monkeypatch):
        if not ncore.available():
            pytest.skip("no native toolchain")
        monkeypatch.delenv("HVTPU_FORCE_PY_GP", raising=False)
        from horovod_tpu.obs import gaussian_process as gpmod

        xs, ys, cand = self._data(seed=9)
        gp = gpmod.GaussianProcess(length_scale=0.3, noise=1e-4)
        gp.fit(xs, ys)
        mu_native, _ = gp.predict(cand)        # native route
        monkeypatch.setenv("HVTPU_FORCE_PY_GP", "1")
        mu_numpy, _ = gp.predict(cand)         # twin route
        np.testing.assert_allclose(mu_native, mu_numpy, atol=1e-10)

    def test_singular_gram_falls_back(self):
        if not ncore.available():
            pytest.skip("no native toolchain")
        # duplicate points with zero noise -> non-PD Gram; native
        # returns None and the numpy twin (with jitter) still answers
        xs = np.zeros((4, 2))
        ys = np.ones(4)
        out = ncore.gp_predict(xs, ys, np.zeros((1, 2)),
                               length_scale=0.3, noise=0.0,
                               signal_variance=1.0)
        assert out is None

    def test_shape_mismatch_raises(self):
        if not ncore.available():
            pytest.skip("no native toolchain")
        xs, ys, _ = self._data()
        with pytest.raises(ValueError, match="shape mismatch"):
            ncore.gp_predict(xs, ys, np.zeros((4, xs.shape[1] + 1)),
                             length_scale=0.3, noise=1e-4,
                             signal_variance=1.0)
        with pytest.raises(ValueError, match="shape mismatch"):
            ncore.gp_expected_improvement(
                xs, ys[:-1], np.zeros((4, xs.shape[1])),
                length_scale=0.3, noise=1e-4, signal_variance=1.0,
                best_y=0.0, xi=0.01,
            )


class TestWheelBuild:
    """pip install compiles the native core into the wheel (parity:
    the reference's setup.py/CMake build — SURVEY.md §2.3: 'pip
    install . on a clean box yields the C++ path')."""

    def test_wheel_contains_loadable_native_core(self, tmp_path):
        import ctypes
        import glob
        import subprocess
        import sys
        import zipfile

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        wheel_dir = tmp_path / "whl"
        r = subprocess.run(
            [sys.executable, "-m", "pip", "wheel", "--no-deps",
             "--no-build-isolation", "-w", str(wheel_dir), repo],
            capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        (whl,) = glob.glob(str(wheel_dir / "*.whl"))
        names = zipfile.ZipFile(whl).namelist()
        assert "horovod_tpu/native/libhvt_core.so" in names

        # the wheel's artifact loads standalone and speaks the exact
        # ABI core.py expects — i.e. an installed user gets the C++
        # control plane, not the Python twin
        site = tmp_path / "site"
        zipfile.ZipFile(whl).extractall(site)
        lib = ctypes.CDLL(str(site / "horovod_tpu/native/libhvt_core.so"))
        lib.hvt_abi_version.restype = ctypes.c_int
        assert lib.hvt_abi_version() == 5
