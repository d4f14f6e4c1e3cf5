"""The system under test, built from a configuration file.

A configuration file (``configs/<name>.json``) holds the model as it is
run, under the published config's key names, and the serving deployment
(``serving``: the ``DisaggConfig`` sizes for one chip). Its ``model_type``
names its family, ``families/<model_type>.py``, which maps the file to the
program's ``ArchConfig``, refuses what the program does not build, and
brings the family's reference and FLOP count (``families/__init__.py``).
A configuration of a new family adds that module and edits nothing here.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from typing import Any, Dict

FAMILIES = Path(__file__).resolve().parent / "families"


def family(conf: Dict[str, Any]):
    """The family module of a configuration, or of its ``dims``."""
    t = conf["model_type"]
    if not (FAMILIES / f"{t}.py").is_file():
        known = sorted(p.stem for p in FAMILIES.glob("*.py")
                       if p.stem != "__init__")
        raise ValueError(f"model_type {t!r}: no family"
                         f" {FAMILIES.name}/{t}.py; families present:"
                         f" {known}")
    return importlib.import_module(f"{__package__}.families.{t}")


def dims(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The shape of the model, in the reference's own names."""
    return family(conf).dims(conf)


def shrunk(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at its ``rehearse`` sizes (CPU runs and tests)."""
    return dict(conf, **conf["rehearse"])


def arch_config(conf: Dict[str, Any]):
    """The program's ``ArchConfig`` for this file."""
    return family(conf).arch_config(conf)


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
