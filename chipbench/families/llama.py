"""The dense GQA decoder as the program builds it: gated SiLU MLP,
RMSNorm, rotary positions without scaling, no biases. A configuration that
asks for anything else is refused. Its reference is ``reference.py``."""
from __future__ import annotations

from typing import Any, Dict

from ..reference import logits  # noqa: F401  (this family's reference)

#: keys of a published config that change the layer equations, with the
#: only value the program builds (an absent key means that value)
BUILT = {"hidden_act": "silu", "mlp_type": "gated_silu",
         "norm_type": "rms_norm", "use_bias": False, "attention_bias": False,
         "mlp_bias": False, "rope_scaling": None, "sliding_window": None}


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    for k, v in BUILT.items():
        if conf.get(k, v) != v:
            raise ValueError(f"{k}={conf[k]!r}: the program builds only"
                             f" {k}={v!r}")
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "model_type": conf["model_type"],
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


def arch_config(conf: Dict[str, Any]):
    from repro.configs import ArchConfig

    m = dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv=m["n_kv"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        tie_embeddings=m["tied"], norm_eps=m["norm_eps"],
        rope_theta=m["rope_theta"], source=conf["source"])


def matmul_per_token(m: Dict) -> float:
    """Q, K, V and output projections of the model's heads (not the padded
    count) and the three matrices of the gated MLP, in every layer."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"],
                       m["head_dim"], m["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return 2.0 * per_layer * m["n_layers"]
