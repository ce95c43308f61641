"""The check has teeth: with the timed path broken underneath, a tiny CPU
run comes out not correct, and the control (the program's own int8
cascade) fails the kernel comparison."""
import pytest

from chipbench_testutil import load_harness, tiny_run


def _failed(res):
    return {k for k, v in res["checks"].items()
            if not load_harness().passes(v["value"], v["op"], v["limit"])}


@pytest.fixture
def h(monkeypatch):
    mod = load_harness()
    yield mod


def test_altered_answer_is_caught(h, monkeypatch):
    """A UDF label altered where it is produced, on the served path only."""
    orig = h.Model._served_udf

    def served(self, j):
        fn = orig(self, j)

        def altered(x):
            lab = fn(x)
            if self.probe.in_window and len(lab):
                lab = lab.copy()
                lab[::3] = (lab[::3] + 1) % int(self.cfg["n_classes"])
            return lab
        return altered

    monkeypatch.setattr(h.Model, "_served_udf", served)
    res = tiny_run(h, "synth3.scan")
    assert not res["correct"]
    assert _failed(res) & {"false_emits", "recall_min"}


def test_half_of_each_batch_left_out_is_caught(h, monkeypatch):
    orig = h.instrument

    def instrument(engines, scorers, tracker, probe):
        orig(engines, scorers, tracker, probe)
        for eng in engines:
            sub = eng.submit

            def half(indices, rows, _sub=sub, **kw):
                if probe.in_window and len(indices) > 1:
                    k = len(indices) // 2
                    kw = {a: (v[:k] if v is not None else v)
                          for a, v in kw.items()}
                    return _sub(indices[:k], rows[:k], **kw)
                return _sub(indices, rows, **kw)
            eng.submit = half

    monkeypatch.setattr(h, "instrument", instrument)
    res = tiny_run(h, "synth3.scan")
    assert not res["correct"]
    assert "lost_records" in _failed(res)


def test_flipped_gate_decisions_are_caught(h, monkeypatch):
    """A keep mask altered where the fused kernel produces it."""
    from repro.kernels import ops

    orig = ops.CascadeScorer.score_masks

    def flipped(self, x):
        m = orig(self, x).copy()
        m[::7, 0] = ~m[::7, 0]
        return m

    monkeypatch.setattr(ops.CascadeScorer, "score_masks", flipped)
    res = tiny_run(h, "synth3.scan")
    assert not res["correct"]
    assert "kernel_flip_ppm" in _failed(res)


def test_control_int8_cascade_fails_the_kernel_check(h):
    res = tiny_run(h, "synth3.scan", quant_dtype="int8")
    assert not res["correct"]
    assert "kernel_flip_ppm" in _failed(res)
