"""Architectures, found by name.

A configuration file's ``model_type`` key names the module
``bench/arch/<model_type>.py`` of the checkout the harness runs from;
``bench/run.py::load_arch`` loads it from there, and a configuration
without the key, or whose module is missing, stops set-up.  A new
architecture is a new module: nothing else in ``bench/`` names one.  A
module writes its reference from the shared numerics of
``bench/lib/reference.py`` and imports nothing of the program under test
outside ``program_config``.

Functions every module gives (``spec`` is what ``model_spec`` returns,
``doc`` the configuration file as a dict):

``model_spec(doc)``
    The spec record: the configuration's sizes as one frozen, hashable
    record.
``make_weights(spec, key, devices=None)``
    The weights, random from the PRNG ``key``, made on the device in one
    jitted call in the dtype the configuration serves.  ``devices`` is the
    cell's list of devices (``None``: JAX's default placement).
``weight_shapes(spec)``
    The tree ``make_weights`` returns, as shapes only.
``program_config(spec)``
    The program's ``repro.models.model.ModelConfig`` for ``spec``.
``served_gap(spec, params, prompt, served)``
    The reference, teacher-forced on ``prompt`` and the ``served`` tokens:
    the widest gap by which a served token's logit lies below the best at
    its position.
``control_gap(spec, params, prompt, served)``
    The control (mode ``"lower"``: one precision step below the
    configuration) read at the same positions: the reference gap of the
    token that the lower precision puts first.
``decode_flops(spec, ctx_sum, n_tokens)``
    Model FLOPs of ``n_tokens`` decode steps whose contexts add up to
    ``ctx_sum``; each yields logits.
``prefill_flops(spec, start, n, head_tokens)``
    Model FLOPs of prompt positions ``start .. start+n-1`` through every
    layer (causal: position p sees p+1 keys), with the LM head for
    ``head_tokens`` of them.
``head_params(spec)``
    Weights one token multiplies by in the LM head.
``paged_attn_cost(spec, ctxs, kv_itemsize, q_itemsize)``
    One paged decode-attention kernel call of one layer over rows whose
    true context lengths are ``ctxs``: (FLOPs, bytes).  The reader
    multiplies it by ``spec.n_layers``.

The counts are the algorithm's, whatever implements it: a padded slab row
or a discarded decode step is not model work.

Spec fields every module gives, and the only ones code outside
``bench/arch/`` reads: ``spec.name``, ``spec.n_layers``,
``spec.vocab_size``, ``spec.dtype`` (``"bfloat16"`` or ``"float32"``, as
served) and ``spec.norm_eps`` (the final norm's epsilon).
"""
