"""A run whose timed path is broken underneath comes out not correct.

Each case drives a whole run (set-up, window, reference check) on the CPU
at the rehearsal sizes, with no look for a chip, with one fault planted in
the served path: a decode step that returns its cache unchanged, half of
the decode batch left out, a decoded token altered where it is produced,
a prefill's token altered where it is produced. One chip per cell, so
there is no exchange between chips to leave out.
"""
import jax.numpy as jnp
import pytest

from chipbench.spec import load_cell

SEED = 2**31 + 99


def _wrap_step_fn(monkeypatch, change):
    """``change(decode batch, (logits, new cache), old cache)`` is what the
    jitted decode step returns instead."""
    from repro.serving.engine import DecodeBatch
    build = DecodeBatch._build

    def broken_build(self, example):
        build(self, example)
        fn = self._step_fn
        self._step_fn = lambda p, c, t, pos: change(self, fn(p, c, t, pos),
                                                    c)
    monkeypatch.setattr(DecodeBatch, "_build", broken_build)


def state_unchanged(monkeypatch):
    _wrap_step_fn(monkeypatch, lambda db, out, old: (out[0], old))


def half_batch(monkeypatch):
    # half of the live slots (every other one) are left out of the step:
    # their logits come out empty
    def change(db, out, old):
        left_out = jnp.asarray(sorted(db.slots)[::2], jnp.int32)
        return out[0].at[left_out].set(0.0), out[1]
    _wrap_step_fn(monkeypatch, change)


def decode_token(monkeypatch):
    from repro.serving.engine import DecodeBatch
    step = DecodeBatch.step

    def broken(self):
        return {rid: (t + 1) % 7 for rid, t in step(self).items()}
    monkeypatch.setattr(DecodeBatch, "step", broken)


def prefill_token(monkeypatch):
    from repro.serving.engine import ServingEngine
    prefill = ServingEngine.prefill

    def broken(self, *a, **kw):
        first, cache, logits = prefill(self, *a, **kw)
        return (first + 1) % 7, cache, logits
    monkeypatch.setattr(ServingEngine, "prefill", broken)


#: cells, with the factor their outputs are cut by: some 16 tokens at the
#: median, so that decode faults have steps to show in
CELLS = {"smollm-360m.code-reuse": 1, "smollm-360m.chat-burst": 8}


def _run(name, monkeypatch):
    from chipbench import harness
    monkeypatch.setattr(harness, "REHEARSE_OUT_DIV", CELLS[name])
    from chipbench.harness import init_jax
    from chipbench.run import run_cell
    cell = load_cell(name)
    assert init_jax(cell.chips, rehearse=True) is None
    return run_cell(cell, SEED, 4.0, trace=False, rehearse=True,
                    log=lambda s: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, monkeypatch):
    r = _run(name, monkeypatch)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   decode_token, prefill_token])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    r = _run(name, monkeypatch)
    assert not r["correct"], r["checks"]


def test_closed_server_is_freed(monkeypatch):
    """Set-up freezes its objects out of garbage collection; closing the
    bench still frees the server, so that the next server of a sweep, or
    the reference after a run, finds the chip's memory free."""
    import weakref

    from chipbench.harness import close_bench, init_jax, open_bench
    cell = load_cell("smollm-360m.chat-burst")
    assert init_jax(cell.chips, rehearse=True) is None
    b = open_bench(cell, SEED, 2.0, rehearse=True)
    srv = weakref.ref(b.srv)
    close_bench(b)
    assert srv() is None
