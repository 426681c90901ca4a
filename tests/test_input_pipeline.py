"""Input-pipeline building blocks (data/prefetch.py): data echoing,
double-buffered H2D staging, and the prefetch worker-error contract.

ISSUE 13 acceptance, unit-sized: E echoes of one shipped batch carry E
DISTINCT augmentation draws over the SAME pixel payload; E=1 is a strict
passthrough (bit-identical trajectory); the stager keeps at most
``slots`` transfers in flight and emits closed h2d_stage events; a
worker exception reaches the consumer at most once, with the original
traceback, after the items produced before the failure.
"""

import threading
import traceback

import numpy as np
import jax
import pytest

from sparknet_tpu.data.prefetch import (PrefetchIterator, H2DStager,
                                        EchoIterator)


def _batches(n, shape=(4, 8), seed=0):
    rs = np.random.RandomState(seed)
    for i in range(n):
        yield {"data": rs.rand(*shape).astype(np.float32),
               "label": np.full(shape[0], i, np.int32)}


# ---------------------------------------------------------------- echoing

class TestEchoIterator:
    def test_each_echo_is_a_distinct_draw_over_shared_pixels(self):
        draws = []

        def fresh_aux(batch):
            aux = {"data#y": np.random.RandomState(
                len(draws)).randint(0, 9, 4)}
            draws.append(aux["data#y"])
            return aux

        src = ({"data": np.full((4, 8), i, np.float32),
                "data#y": np.zeros(4, np.int64)} for i in range(3))
        it = EchoIterator(src, echo=3, fresh_aux=fresh_aux)
        got = [next(it) for _ in range(9)]
        for base in range(3):
            fam = got[3 * base:3 * base + 3]
            for echo in fam[1:]:
                # the pixel payload is REUSED by reference (that's the
                # whole point: no re-transfer), the aux is re-drawn
                assert echo["data"] is fam[0]["data"]
                assert not np.array_equal(echo["data#y"],
                                          fam[0]["data#y"])
        # E-1 fresh draws per base batch, all distinct
        assert len(draws) == 3 * 2
        with pytest.raises(StopIteration):
            next(it)

    def test_echo_one_is_strict_passthrough(self):
        items = [dict(b) for b in _batches(4)]
        calls = []
        it = EchoIterator(iter(items), echo=1,
                          fresh_aux=lambda b: calls.append(b) or {})
        out = list(it)
        assert [o is i for o, i in zip(out, items)] == [True] * 4
        assert calls == []              # no rng burned, bit-identical

    def test_echo_one_trajectory_bit_identical_through_prefetch(self):
        def consume(wrap):
            it = PrefetchIterator(_batches(6, seed=7), depth=2)
            if wrap:
                it = EchoIterator(it, echo=1)
            with it:
                return [float(np.sum(b["data"]) + np.sum(b["label"]))
                        for b in it]
        assert consume(False) == consume(True)

    def test_delegates_stats_and_close(self):
        src = PrefetchIterator(_batches(2), depth=1, extra={"k": 1})
        it = EchoIterator(src, echo=2)
        next(it)
        st = it.stats()
        assert st["echo"] == 2 and st["k"] == 1
        it.close()
        for t in src._threads:
            t.join(timeout=5)
            assert not t.is_alive()

    def test_close_before_a_waiting_put_lands_leaks_no_worker(self):
        """The order a loaded machine produces: the worker's put of batch
        1 is already waiting on the full queue when the consumer takes
        batch 0 and closes, and it lands after close() has drained. The
        worker's end marker then finds the queue full with nobody left
        to make room; it must give up, not wait for ever."""
        patched, waiting, closed = (threading.Event() for _ in range(3))

        def source():
            gen = _batches(2)
            yield next(gen)
            patched.wait(5)
            yield next(gen)

        src = PrefetchIterator(source(), depth=1)
        real_put = src._q.put

        def late_put(item, *a, **kw):
            if isinstance(item, dict) and item["label"][0] == 1:
                waiting.set()
                closed.wait(5)
            return real_put(item, *a, **kw)

        src._q.put = late_put
        patched.set()
        assert next(src)["label"][0] == 0
        assert waiting.wait(5)
        src.close()
        closed.set()
        for t in src._threads:
            t.join(timeout=5)
            assert not t.is_alive()


# ---------------------------------------------------------------- staging

class _Sink:
    def __init__(self):
        self.events = []

    def log(self, event, **kw):
        self.events.append(dict(kw, event=event))


class TestH2DStager:
    def test_puts_device_arrays_bounded_ring(self):
        ml = _Sink()
        st = H2DStager(slots=2, metrics=ml, emit_every=2)
        for i, b in enumerate(_batches(5)):
            out = st(b)
            assert isinstance(out["data"], jax.Array)
            np.testing.assert_array_equal(
                np.asarray(out["label"]), b["label"])
            assert st.stats()["in_flight"] <= 2
        s = st.stats()
        assert s["puts"] == 5
        assert s["bytes"] == 5 * sum(v.nbytes for v in b.values())
        st.flush()
        assert st.stats()["in_flight"] == 0
        ev = [e for e in ml.events if e["event"] == "h2d_stage"]
        assert [e["puts"] for e in ev] == [2, 4]    # emit_every=2
        for e in ev:                                # closed-schema fields
            assert {"name", "puts", "bytes", "kb_per_item", "dispatch_ms",
                    "wait_ms", "in_flight", "slots"} <= set(e)

    def test_single_leaf_and_chaos_hook(self):
        class _Chaos:
            slow_h2d = 0.001
            calls = []

            def maybe_slow_h2d(self, nbytes=0):
                self.calls.append(int(nbytes))
                return 0.0

        ch = _Chaos()
        st = H2DStager(slots=1, chaos=ch)
        x = np.arange(12, dtype=np.float32)
        out = st(x)
        assert isinstance(out, jax.Array)
        assert ch.calls == [x.nbytes]   # charged the actual wire bytes
        st.flush()


# ------------------------------------------------- worker-error contract

class TestPrefetchErrorPropagation:
    def _mid_stream_raiser(self, good=3):
        yield from _batches(good)
        raise RuntimeError("disk on fire")

    def test_error_after_good_items_once_with_traceback(self):
        it = PrefetchIterator(self._mid_stream_raiser(), depth=2)
        got = [next(it)["label"][0] for _ in range(3)]
        assert got == [0, 1, 2]         # pre-failure items arrive first
        with pytest.raises(RuntimeError, match="disk on fire") as ei:
            next(it)
        frames = traceback.extract_tb(ei.value.__traceback__)
        assert any(f.name == "_mid_stream_raiser" for f in frames), \
            "original worker traceback was lost"
        # at most once: the stream is then cleanly exhausted, not a
        # second raise on every subsequent next()
        with pytest.raises(StopIteration):
            next(it)
        with pytest.raises(StopIteration):
            next(it)

    def test_immediate_failure_two_workers_no_wedge(self):
        def boom():
            raise ValueError("bad shard")
            yield  # pragma: no cover

        it = PrefetchIterator(boom(), depth=2, workers=2)
        with pytest.raises(ValueError, match="bad shard"):
            next(it)
        with pytest.raises(StopIteration):
            next(it)
        for t in it._threads:
            t.join(timeout=5)
            assert not t.is_alive()     # siblings released, no deadlock

    def test_close_before_error_drops_it(self):
        it = PrefetchIterator(self._mid_stream_raiser(good=1), depth=2)
        next(it)
        it.close()                      # consumer stops first: no raise

    def test_transform_errors_propagate_same_contract(self):
        def bad_transform(b):
            if b["label"][0] >= 2:
                raise KeyError("transform blew up")
            return b

        it = PrefetchIterator(_batches(5), depth=2,
                              transform=bad_transform)
        assert next(it)["label"][0] == 0
        assert next(it)["label"][0] == 1
        with pytest.raises(KeyError, match="transform blew up"):
            next(it)
        with pytest.raises(StopIteration):
            next(it)
