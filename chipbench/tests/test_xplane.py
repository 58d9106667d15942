"""The trace reduction: on a hand-made trace whose answers are known,
and on a small trace recorded on a TPU v5e (``record_trace.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import xplane

DATA = Path(__file__).resolve().parent / "data"


def _space(device_ops, host_spans, modules=()):
    """An XSpace text proto: device ops, the executables they ran in and
    host spans, each as (name, start ns, duration ns)."""
    def plane(pid, name, lines):
        names = sorted({n for _, events in lines for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = ""
        for lid, (line, events) in enumerate(lines, 1):
            evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                          f"{s * 1000} duration_ps: {d * 1000} }}\n"
                          for n, s, d in events)
            body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
                     f'{evs} }}\n')
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}" {body}{meta} }}\n'
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        plane(1, "/device:TPU:0", [(xplane.OPS_LINE, device_ops),
                                   (xplane.MODULES_LINE, list(modules))])
        + plane(2, "/host:CPU", [("python", host_spans)]))


def test_busy_idle_kernel_and_gap_labels():
    pd = _space(
        device_ops=[("%fusion.3 = bf16[4,8]{1,0} fusion(x)", 100, 200),
                    ("%paged_decode_attention.1 = bf16[2,8] custom-call()",
                     250, 100),
                    ("%fusion.4 = (f32[2], s32[]) fusion(y)", 300, 100),
                    ("%while.2 = (s32[]) while(z)", 90, 330),  # container
                    ("%paged_decode_attention.2 = bf16[2,8] custom-call()",
                     700, 50),
                    ("%copy.1 = bf16[4]{0} copy(w)", 2000, 100)],  # late
        host_spans=[(xplane.WINDOW_SPAN, 0, 1000), ("step#0", 0, 420),
                    ("bookkeeping", 420, 80), ("wait", 500, 150),
                    ("step#1", 650, 300), ("jit(something)", 0, 1000)],
        modules=[("jit__mixed_step(123)", 90, 330),
                 ("jit__decode_paged(456)", 690, 70)])
    labels = {"step#0": "step.mixed", "step#1": "step.decode"}
    red = xplane.reduce(pd, kernels=("paged_decode_attention",),
                        labels=lambda n: labels.get(
                            xplane.harness_span(n), xplane.harness_span(n)))
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400) and [700, 750); the while's own event is left out
    assert red["busy_s"] == pytest.approx(350e-9)
    assert red["kernel_s"]["paged_decode_attention"] == pytest.approx(150e-9)
    assert red["kernel_calls"]["paged_decode_attention"] == 2
    gaps = dict(red["idle_gaps"])
    # idle: [0,100) step.mixed; [400,700): wait 150, bookkeeping 80,
    # step.decode 50 -> wait; [750,1000) step.decode
    assert gaps == pytest.approx({"step.mixed": 100e-9, "wait": 300e-9,
                                  "step.decode": 250e-9})
    assert dict(red["device_ops"]) == pytest.approx({
        "jit__mixed_step/fusion.3 bf16[4,8]": 200e-9,
        "jit__mixed_step/paged_decode_attention.1 bf16[2,8]": 100e-9,
        "jit__mixed_step/fusion.4 f32[2]": 100e-9,
        "jit__decode_paged/paged_decode_attention.2 bf16[2,8]": 50e-9})


def test_a_trace_without_the_window_span_is_refused():
    pd = _space([("%fusion.1 = f32[2] fusion()", 0, 10)],
                [("step#0", 0, 10)])
    with pytest.raises(ValueError):
        xplane.reduce(pd)


def test_recorded_tpu_trace():
    """qwen2-vl-2b's rehearsal steps on one v5e chip, traced from the
    first step: every decode-bearing step runs the paged kernel once
    per layer, the busy time lies inside the window, and the labelled
    idle gaps add up to the idle time."""
    facts = json.loads((DATA / "window.json").read_text())
    kinds = {f"step#{i}": f"step.{k}" for i, k in enumerate(facts["steps"])}
    red = xplane.reduce(xplane.load(DATA / "window.xplane.pb.gz"),
                        kernels=("paged_decode_attention",),
                        labels=lambda n: kinds.get(xplane.harness_span(n),
                                                   xplane.harness_span(n)))
    steps = [k for k in facts["steps"] if k in ("decode", "mixed")]
    assert red["kernel_calls"]["paged_decode_attention"] == \
        facts["n_layers"] * len(steps)
    assert 0 < red["kernel_s"]["paged_decode_attention"] < red["busy_s"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
