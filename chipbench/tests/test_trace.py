"""The reduction from a profiler trace to metrics."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace

OPS = [("fusion.1", 0, 10), ("flash_attention.3", 5, 20),
       ("fusion.2", 30, 40), ("flash_attention", 38, 45),
       ("copy.7", 60, 70)]


def test_union_busy_and_gaps():
    assert trace.union([(5, 20), (0, 10), (30, 40), (38, 45)]) == \
        [(0, 20), (30, 45)]
    assert trace.busy_ns(OPS) == 20 + 15 + 10
    assert trace.idle_gaps(OPS, (0, 100)) == [(20, 30), (45, 60), (70, 100)]
    assert trace.idle_gaps(OPS, (-5, 70)) == [(-5, 0), (20, 30), (45, 60)]


def test_kernel_events_and_top_ops():
    assert trace.by_name(OPS, trace.FLASH) == (2, 15 + 7)
    assert not trace.FLASH.match("flash_attention_grad.1")
    top = dict(trace.top_ops(OPS))
    assert top["flash_attention"] == pytest.approx(22e-9)
    assert top["fusion"] == pytest.approx(20e-9)


def test_idle_by_host_names_the_innermost_span():
    host = [("chipbench.serve", 0, 50), ("chipbench.prefill", 18, 35),
            ("chipbench.idle", 55, 100)]
    got = dict(trace.idle_by_host(trace.idle_gaps(OPS, (0, 100)), host))
    # (20, 30) in the prefill, (45, 60) between spans, (70, 100) idle
    assert got == pytest.approx({"chipbench.prefill": 10e-9,
                                 "untraced": 15e-9,
                                 "chipbench.idle": 30e-9})


def test_window_and_host_spans_from_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.serve"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    assert 0.015 < t.window_s < 5.0
    serve = [h for h in t.host if h[0] == "chipbench.serve"]
    assert len(serve) == 3
    assert all(t.window[0] <= s < e <= t.window[1] for _, s, e in serve)


def test_op_names_and_containers():
    assert trace.op_name("%flash_attention.3 = bf16[32,128,128]{2,1,0} "
                         "custom-call(bf16[32,128,128] %a)") == \
        "flash_attention.3"
    ops = [("while.13", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 60)]
    assert dict(trace.top_ops(ops)) == pytest.approx({"fusion": 40e-9})
    assert trace.busy_ns(ops) == 100
