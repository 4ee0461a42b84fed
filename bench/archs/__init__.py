"""Architectures: one module per architecture, ``archs/<arch>.py``.

A configuration file names its architecture with the key ``"arch"``
(``"dense"`` when the key is absent); the harness loads the module by
that name (``harness.spec.load_arch``) and reaches the model only
through it. Each module provides:

- ``spec(config) -> Spec``: the sizes it reads from the configuration's
  Hugging Face keys, as a frozen, hashable dataclass (a static argument
  of its jitted functions) with at least ``name``, ``layers``,
  ``d_model``, ``vocab`` and ``dtype`` (the type the weights are served
  in), which the harness and the traffic read.
- ``program_config(spec)``: the program's ``ModelConfig``.
- ``program_params(key, spec)``: the program's parameter tree, made on
  the device in one jitted call from ``harness.weights.root_key(seed)``.
- ``gaps(spec, seed, seqs, pad_to, control=False)``: the float32
  reference's gap of every served token below its best logit, and with
  ``control`` the float8 control's (see ``harness.reference``). The
  reference draws its weights again from the seed, layer by layer, and
  imports nothing of the program.
- Counts of the work asked for, from shapes (one multiply-add is two
  operations; never the padding, table buckets or grid a kernel runs):
  ``param_count(spec)``, ``kv_bytes_per_token(spec)``,
  ``token_flops(spec, context, logits)``, ``prompt_flops(spec, n)``,
  ``decode_attn_work(spec, context, spans)`` and
  ``prefill_attn_work(spec, n_query, prefix, spans)``, the last two as
  (operations, bytes).

What stays in ``harness/``: seeds and draws (``harness.weights``:
``root_key``, ``layer_key``, ``normal``, ``matrix``, ``norm_scale``), the
float32 matmul, ``fp8_round``, the packing of sampled sequences, the
blocked head-and-gap loop and ``widest`` (``harness.reference``). So an
architecture writes only its own layers.
"""
