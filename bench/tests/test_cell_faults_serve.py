"""A serving run with the server broken underneath reads
`correct: false`; unbroken, it reads true."""
import numpy as np
import pytest

import cell_small


def test_sound_run_is_correct():
    line = cell_small.run("higgs.serve")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["window_compiles"] == 0


def _stale(predict):
    """Hands back the previous answer (its state unchanged)."""
    last = {}

    def wrapped(self, num, cat=None):
        out = np.asarray(predict(self, num, cat))
        prev = last.get(out.shape, out)
        last[out.shape] = out
        return prev
    return wrapped


def _half_batch(predict):
    """Answers every row from the first half of the request."""
    def wrapped(self, num, cat=None):
        num = np.asarray(num)
        k = max(1, len(num) // 2)
        return np.asarray(predict(self, np.concatenate(
            [num[:k], num[:len(num) - k]]), cat))
    return wrapped


def _altered(predict):
    def wrapped(self, num, cat=None):
        out = np.array(predict(self, num, cat))
        out[0, 0] += 1e-3
        return out
    return wrapped


def _raises(predict):
    """Fails every 8-row request once the server has run a while."""
    calls = [0]

    def wrapped(self, num, cat=None):
        calls[0] += 1
        if len(num) == 8 and calls[0] > 100:
            raise RuntimeError("injected")
        return predict(self, num, cat)
    return wrapped


@pytest.mark.parametrize("fault", [_stale, _half_batch, _altered, _raises],
                         ids=["unchanged_state", "half_batch",
                              "altered_answer", "failed_request"])
def test_broken_run_is_not_correct(monkeypatch, fault):
    from repro.serve.engine import ForestServer
    monkeypatch.setattr(ForestServer, "predict", fault(ForestServer.predict))
    line = cell_small.run("higgs.serve")
    assert not line["correct"], line["checks"]
