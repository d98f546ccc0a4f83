"""With the timed path broken underneath, a run's ``correct`` comes out
false: once for each fault the cell can have.  The chip check is skipped
(smoke sizes on the CPU); the rest of the run is the real harness."""

import numpy as np
import pytest

from chipbench.tests import smoke


def _state_unchanged(monkeypatch):
    from repro.serving.decode_runtime import PagedDecoder

    orig = PagedDecoder._step
    monkeypatch.setattr(PagedDecoder, "_step",
                        lambda self, state: (state, orig(self, state)[1]))


def _token_altered(monkeypatch):
    from repro.serving.decode_runtime import PagedDecoder

    orig = PagedDecoder._step

    def step(self, state):
        new, tok = orig(self, state)
        return new, (tok + 1) % self.cfg.vocab

    monkeypatch.setattr(PagedDecoder, "_step", step)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_serving_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = smoke.run(smoke.serve_cell())
    assert not r["correct"], r["checks"]


def _reduce_unchanged(monkeypatch):
    from repro.core import device_shuffle as ds

    monkeypatch.setattr(ds, "device_segment_reduce",
                        lambda ids, values, n, *a, **k: np.zeros(n, np.int64))


def _answer_altered(monkeypatch):
    from repro.core import device_shuffle as ds

    orig = ds.device_segment_reduce

    def reduce(*a, **k):
        out = np.array(orig(*a, **k))
        out[0] += 1
        return out

    monkeypatch.setattr(ds, "device_segment_reduce", reduce)


def _half_batch(monkeypatch):
    from repro.core import device_shuffle as ds

    orig = ds.device_partition

    def partition(*a, **k):
        parts, overflow = orig(*a, **k)
        return [p[::2] for p in parts], overflow

    monkeypatch.setattr(ds, "device_partition", partition)


def _exchange_left_out(monkeypatch):
    from repro.core import mapreduce

    orig = mapreduce._decode_pairs
    calls = {"n": 0}

    def decode(blob):
        calls["n"] += 1
        return [] if calls["n"] % 7 == 1 else orig(blob)

    monkeypatch.setattr(mapreduce, "_decode_pairs", decode)


@pytest.mark.parametrize("fault", [_reduce_unchanged, _answer_altered,
                                   _half_batch, _exchange_left_out])
def test_wordcount_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = smoke.run(smoke.wordcount_cell())
    assert not r["correct"], r["checks"]
