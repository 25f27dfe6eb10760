"""Model families: the code of one architecture, found by the name its
configuration already carries.

A configuration (``bench/configs/<name>.json``, Hugging Face key names)
names its family by ``model_type``.  ``bench.modeldef.family(cfg)``
loads ``bench/families/<model_type>.py`` by path, with ``.`` and ``-``
in the name turned into ``_``, and fails before any weight is made
where the key or the file is missing.  A new architecture is a new
module here beside its configuration, traffic and cell files: no file
that exists needs an edit.

A family module exposes four names; the harness uses nothing else of it.

``model_config(cfg)``
    The program's ``repro.configs.base.ModelConfig`` for the
    configuration.  Raises ``ValueError`` for a setting the program's
    model cannot express, rather than serve something else under the
    configuration's name.

``init_fn(cfg)``
    ``key -> params``: a pure function that ``modeldef.make_params``
    jits once, so every leaf is made on the device in one call, in the
    configuration's ``torch_dtype``.  The tree is the program's parameter
    layout: ``modeldef.check_layout`` compares it with
    ``jax.eval_shape(Model.init)`` before the weights are made.  Draw
    biases and norm scales too, so those paths are compared.

``Reference(cfg)``
    The plain reference: the published forward pass in float32
    ``jax.numpy`` with every product at ``bench.reference.HI``.  It
    imports nothing of the program and reads only the configuration and
    the weights it is given.  It has two methods:

    - ``logits(params, tokens, positions, quant=None)``: float32 logits
      ``[len(positions), vocab]`` of the sequence ``tokens`` at
      ``positions``, each predicting the token after it.  ``quant``
      names a precision of ``bench.reference.LOW``: every weight product
      and the stored keys and values rounded to it (the control).
    - ``served_gaps(params, prompt, served, controls=())``: for one
      served request, ``{"gap": ...}``, at each position that produced a
      served token how far that token's logit lies below the reference's
      best, and for each precision ``q`` in ``controls`` the same for the
      token that the forward in ``q`` puts first, under ``gap_<q>``.

``Shapes.of(cfg)``
    The work the model needs, whatever implements it: no padding, masked
    position or copy is counted, and a product of ``m x k`` by ``k x n``
    is ``2 m k n`` operations.  Metrics read it as ``ctx.shapes``:
    ``params``, ``weight_bytes`` (what one decode step reads of the
    weights), ``kv_bytes_per_token`` (the cache one position holds over
    all layers), ``token_flops(ctx)`` (one token attending to ``ctx``
    positions, no logits), ``prefill_flops(n, start=0)`` (``n`` prompt
    tokens from position ``start`` and the last one's logits),
    ``decode_flops(contexts)`` and ``decode_bytes(contexts)`` (one
    decode step over live rows with these contexts, the new token
    included).

Shared by every family: ``bench/modeldef.py`` (``load_config``,
``seed_key``, ``make_params``, ``check_layout``), ``bench/reference.py``
(the control precisions ``fake_int8``, ``fake_fp8``, ``LOW`` and
``HI``) and ``bench/counts.py`` (``least_time``, ``DTYPE_BYTES``).
"""
