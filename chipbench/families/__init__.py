"""Model families, one module each, found by a configuration's
``model_type``: ``model.family`` loads ``families/<model_type>.py``, so a
configuration of a new family comes with a module here and edits none.

A family module provides:

* ``dims(conf) -> dict``: the model's shape in its reference's names,
  with at least ``model_type``, ``vocab``, ``max_len``, ``n_layers``,
  ``n_heads``, ``n_kv``, ``head_dim`` and ``d_model`` (the harness, the
  traffic and the attention counts of ``flops.py`` read these); it raises
  ``ValueError`` for a key of the configuration that the program does not
  build as stated;
* ``arch_config(conf)``: the program's ``ArchConfig`` for it;
* ``logits(m, seed, seqs, rows, fp8=False) -> list``: the plain float32
  reference, per sequence the logits ``[len(rows), vocab]`` at ``rows``,
  with weights drawn from ``seed`` as the served model draws them; with
  ``fp8=True`` the control, its weight matmuls in float8;
* ``matmul_per_token(m) -> float``: the FLOPs of all weight matmuls for
  one token (logits left out).

``m`` is what ``dims`` returned.

A family also brings its own reference tests,
``tests/test_family_<model_type>.py``, parametrised over the
configurations of its ``model_type`` at their ``rehearse`` sizes
(``tests/test_discovery.py`` refuses a family without them):

* its weights drawn as the served model draws them;
* its reference logits, through ``model.family(conf).logits``, against
  the served prefill, within a tolerance of its own and the reason for it
  (bfloat16 against float32 for the dense decoder; top-k routing that can
  flip on bfloat16 rounding needs its own argument);
* its float8 control, off the reference by more than rounding.
"""
