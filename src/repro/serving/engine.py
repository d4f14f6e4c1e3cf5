"""JAX serving engine: jitted prefill / suffix-prefill and a slotted
continuous-batching decode loop.

The decode loop keeps one stacked cache pytree of fixed capacity
(``max_slots`` sequences x ``capacity`` tokens) and vmaps
``Model.decode_step`` over slots with **per-slot positions** — the vmapped
``dynamic_update_slice`` writes each sequence at its own offset, which is
what lets sequences of different lengths share a batch (continuous
batching). Slots are recycled as sequences retire; inactive slots still
compute (dead lanes) and are masked out of the results, exactly as a
fixed-shape TPU serving binary would.

The step's inputs ``tok`` ([max_slots, 1, 1]) and ``pos`` ([max_slots])
live on the host as int32 NumPy arrays: ``add`` and the bookkeeping after a
step write them there, and each step uploads both in one explicit
``jax.device_put``, uncommitted to the default device as every other input
of the step is (a committed input would commit the step's outputs, and the
stacked cache's first write after a step would compile anew). Each step
then reads the device once, the ``[max_slots]`` greedy next tokens, whatever
the number of live slots. Dead lanes keep their last token and position.

The jitted entries are named ``prefill_full``, ``prefill_suffix``,
``decode_step`` and ``decode_argmax``: a profile's XLA modules and the
per-entry compile counter (``wall_spans.compiles``) say which program
compiled. Importing this module gates the wall-clock span channel on the
profiler (``_install_wall_spans``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.telemetry import wall_spans
from ..models.lm import Model
from .paged_kv import is_token_leaf_path

__all__ = ["ServingEngine", "DecodeBatch"]

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _install_wall_spans() -> None:
    """Record the channel's spans exactly while a profiler session is active,
    each also as a ``TraceAnnotation`` of the same name (so it lands in the
    profile on the trace's clock), and feed its compile counter from
    ``jax.monitoring``."""
    ann = jax.profiler.TraceAnnotation
    wall_spans.install(ann.is_enabled, ann)
    counter = wall_spans.compiles

    def on_duration(event, secs, fun_name="", **_):
        if event == _BACKEND_COMPILE:
            counter.compiled(fun_name, secs)

    def on_event(event, **_):
        if event == _CACHE_HIT:
            counter.cache_hit()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


_install_wall_spans()


class ServingEngine:
    """Prefill-side engine for one serving unit."""

    def __init__(self, model: Model, params: Any):
        self.model = model
        self.params = params

        def prefill_full(p, b):
            return model.prefill(p, b)

        def prefill_suffix(p, b, caches, pos):
            return model.prefill(p, b, caches=caches, pos=pos)

        self._full = jax.jit(prefill_full)
        self._suffix = jax.jit(prefill_suffix)

    def prefill(self, tokens: np.ndarray,
                prefix_cache: Optional[Any] = None,
                prefix_len: int = 0,
                extra: Optional[Dict[str, Any]] = None
                ) -> Tuple[int, Any, jnp.ndarray]:
        """Prefill one request (B=1). Returns (first_token, cache, logits).

        With ``prefix_cache`` the engine computes only the suffix
        ``tokens[prefix_len:]`` — the compute saving of Stage-1 KV reuse.
        """
        tokens = np.asarray(tokens)
        if prefix_cache is not None and prefix_len > 0:
            batch = {"tokens": jnp.asarray(tokens[None, prefix_len:],
                                           jnp.int32)}
            if extra:
                batch.update(extra)
            logits, cache = self._suffix(self.params, batch, prefix_cache,
                                         jnp.asarray(prefix_len, jnp.int32))
        else:
            batch = {"tokens": jnp.asarray(tokens[None], jnp.int32)}
            if extra:
                batch.update(extra)
            logits, cache = self._full(self.params, batch)
        first = int(jnp.argmax(logits[0, -1]))
        return first, cache, logits


@jax.jit
def decode_argmax(logits):
    """Greedy next token of each slot. Jitted: one dispatch a step, where
    eager slicing would also upload its index scalars."""
    return jnp.argmax(logits[:, 0, -1], axis=-1).astype(jnp.int32)


@dataclass
class _Slot:
    rid: int
    pos: int                 # next write position == current length
    tokens: List[int] = field(default_factory=list)
    max_new: int = 16


class DecodeBatch:
    """Slotted continuous-batching decode engine (one decode unit)."""

    def __init__(self, model: Model, params: Any, capacity: int = 256,
                 max_slots: int = 8):
        self.model = model
        self.params = params
        self.capacity = capacity
        self.max_slots = max_slots
        self.slots: Dict[int, _Slot] = {}
        self._free = list(range(max_slots - 1, -1, -1))
        self._stacked: Optional[Any] = None
        self._tok = np.zeros((max_slots, 1, 1), np.int32)
        self._pos = np.zeros((max_slots,), np.int32)
        self._step_fn = None

    # ------------------------------------------------------------- plumbing
    def _leaf_window(self, path) -> int:
        """Sliding window of the layer owning this cache leaf (0 = full)."""
        try:
            seg = self.model.segments[path[0].idx]
            return seg.kinds[path[1].idx][2]
        except (AttributeError, IndexError):
            return 0

    def _leaf_capacity(self, path) -> int:
        w = self._leaf_window(path)
        return min(self.capacity, w) if w else self.capacity

    def _build(self, example_cache: Any) -> None:
        n = self.max_slots

        def expand(path, leaf):
            # [count, 1, S, ...] token leaf -> [count, n, cap, ...]
            # [count, 1, ...]    state leaf -> [count, n, ...]
            shp = list(leaf.shape)
            shp[1] = n
            if is_token_leaf_path(path):
                shp[2] = self._leaf_capacity(path)
            return jnp.zeros(tuple(shp), leaf.dtype)

        self._stacked = jax.tree_util.tree_map_with_path(expand, example_cache)
        model = self.model

        def decode_step(p, cache, tok, pos):
            # vmap strips the B axis (axis 1); run the model at B=1 inside
            cache = jax.tree.map(lambda x: x[:, None], cache)
            logits, new_cache = model.decode_step(p, cache, tok, pos)
            return logits, jax.tree.map(lambda x: x[:, 0], new_cache)

        self._step_fn = jax.jit(jax.vmap(
            decode_step, in_axes=(None, 1, 0, 0), out_axes=(0, 1)))

    # ------------------------------------------------------------ lifecycle
    def add(self, rid: int, cache: Any, n_tokens: int, first_token: int,
            max_new: int = 16) -> int:
        """Admit a prefilled sequence; returns its slot id."""
        if not self._free:
            raise RuntimeError("decode batch full")
        if self._stacked is None:
            self._build(cache)
        slot = self._free.pop()

        def write(path, big, small):
            x = small[:, 0]                           # [count, S, ...] / [count, ...]
            if is_token_leaf_path(path):
                cap = big.shape[2]
                w = self._leaf_window(path)
                if w and x.shape[1] == cap and n_tokens > cap:
                    # window-cropped leaf holds positions [n-cap, n) at
                    # [0, cap); restore the rolling-buffer invariant
                    # (position p lives at index p % cap) for decode
                    x = jnp.roll(x, (n_tokens - cap) % cap, axis=1)
                pad = cap - x.shape[1]
                if pad < 0:
                    raise ValueError("sequence longer than decode capacity")
                if pad:
                    x = jnp.pad(x, [(0, 0), (0, pad)]
                                + [(0, 0)] * (x.ndim - 2))
            return big.at[:, slot].set(x)

        self._stacked = jax.tree_util.tree_map_with_path(
            write, self._stacked, cache)
        self._tok[slot, 0, 0] = first_token
        self._pos[slot] = n_tokens
        self.slots[slot] = _Slot(rid=rid, pos=n_tokens, tokens=[first_token],
                                 max_new=max_new)
        return slot

    def remove(self, slot: int) -> _Slot:
        s = self.slots.pop(slot)
        self._free.append(slot)
        return s

    # ----------------------------------------------------------------- step
    def step(self) -> Dict[int, int]:
        """One decode step for every active slot. Returns {rid: new_token}
        and retires slots that reached ``max_new`` or capacity."""
        if not self.slots:
            return {}
        live = len(self.slots)
        # spans: the upload and launch, the one read of the next tokens, and
        # the host bookkeeping over live slots
        with wall_spans.span("repro.decode.launch", live=live):
            tok, pos = jax.device_put((self._tok, self._pos))
            logits, self._stacked = self._step_fn(
                self.params, self._stacked, tok, pos)
            nxt = decode_argmax(logits)
        with wall_spans.span("repro.decode.wait", reads=1):
            nxt = jax.device_get(nxt)
        out: Dict[int, int] = {}
        with wall_spans.span("repro.decode.slots", live=live):
            for slot, meta in list(self.slots.items()):
                t = int(nxt[slot])
                meta.tokens.append(t)
                meta.pos += 1
                out[meta.rid] = t
                self._tok[slot, 0, 0] = t
                self._pos[slot] = meta.pos
                if len(meta.tokens) >= meta.max_new \
                        or meta.pos >= self.capacity - 1:
                    self.remove(slot)
        return out

    @property
    def n_active(self) -> int:
        return len(self.slots)
