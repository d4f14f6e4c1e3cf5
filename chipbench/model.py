"""The system under test, built from a configuration file.

A configuration file (``configs/<name>.json``) holds the model as it is
run, under the published config's key names, and the serving deployment
(``serving``: the ``DisaggConfig`` sizes for one chip). Only the dense GQA
decoder the program builds is read here (gated SiLU MLP, RMSNorm, rotary
positions without scaling, no biases); a file that asks for anything else
is refused, and another family adds its own mapping.
"""
from __future__ import annotations

from typing import Any, Dict

DENSE_TYPES = ("llama",)
#: keys of a published config that change the layer equations, with the
#: only value the program builds (an absent key means that value)
BUILT = {"hidden_act": "silu", "mlp_type": "gated_silu",
         "norm_type": "rms_norm", "use_bias": False, "attention_bias": False,
         "mlp_bias": False, "rope_scaling": None, "sliding_window": None}


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The shape of the model, in the reference's own names."""
    if conf["model_type"] not in DENSE_TYPES:
        raise ValueError(f"model_type {conf['model_type']!r}: only dense GQA"
                         f" decoders {DENSE_TYPES} are built here")
    for k, v in BUILT.items():
        if conf.get(k, v) != v:
            raise ValueError(f"{k}={conf[k]!r}: the program builds only"
                             f" {k}={v!r}")
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "d_model": d,
        "n_layers": conf["num_hidden_layers"],
        "n_heads": h,
        "n_kv": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim", d // h),
        "d_ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "tied": bool(conf["tie_word_embeddings"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "rope_theta": float(conf["rope_theta"]),
        "max_len": int(conf["max_position_embeddings"]),
    }


def shrunk(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at its ``rehearse`` sizes (CPU runs and tests)."""
    return dict(conf, **conf["rehearse"])


def arch_config(conf: Dict[str, Any]):
    """The program's ``ArchConfig`` for this file."""
    from repro.configs import ArchConfig

    m = dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv=m["n_kv"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        tie_embeddings=m["tied"], norm_eps=m["norm_eps"],
        rope_theta=m["rope_theta"], source=conf["source"])


def build_server(conf: Dict[str, Any], params_key, policy: str, hw=None):
    """(model, params, server): weights made on the device in one jitted
    call from ``params_key``; ``hw=None`` prices the virtual clock with the
    peaks of the chip the process runs on."""
    import jax

    from repro.core import make_policy
    from repro.models.lm import build_model
    from repro.serving import DisaggConfig, DisaggServer

    model = build_model(arch_config(conf))
    params = jax.block_until_ready(jax.jit(model.init)(params_key))
    s = conf["serving"]
    srv = DisaggServer(model, params, policy=make_policy(policy),
                       cfg=DisaggConfig(
                           n_prefill_units=s["n_prefill_units"], hw=hw,
                           page_size=s["page_size"], n_pages=s["n_pages"],
                           decode_slots=s["decode_slots"],
                           decode_capacity=s["decode_capacity"]))
    return model, params, srv
