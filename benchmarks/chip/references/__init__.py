"""Plain references, one module a model class; a configuration names its own.

A configuration's file may carry ``"reference": "<name>"``; the launcher and
``refcheck`` then load ``references/<name>.py`` by path and call its
``compare``.  A file without the key is judged by ``../reference.py`` (the
Llama/Mistral-class decoder), which keeps this contract too.  A later PR adds
a class by adding a module here and naming it in its configuration's file; it
edits nothing.

The contract of a module:

- ``compare(engine, seed, ref_params=None) -> dict`` with ``ok`` (bool) and
  whatever it measured, as numbers under names that a configuration's
  ``limits/<configuration>.json`` can address by dotted path
  (``both.rms_rel``).  An exception it raises becomes
  ``{"ok": false, "error": ...}`` in the caller.
- The forward it compares with is float32 at ``highest`` matmul precision in
  straightforward ``jax.numpy``: no kernel, no cache, no batching trick, and
  no code shared with ``dynamo_tpu/engine/model.py`` (driving the program,
  ``reference.served_logits``, the arithmetic of the gaps, ``reference.gaps``,
  and the two put together, ``reference.compare_with``, may be shared: they
  are the harness's, not the model's).
- It reads the engine's own weights (``engine.params``), or ``ref_params``
  where the caller hands them over: the weights the same seed draws before
  the engine quantised them, so that a quantised weight path is a control and
  not a second reference.
- It chooses its own sequences and lengths, from ``seed``.  A mechanism that
  only acts past N positions (a selection of the top N keys, a sliding
  window) is compared past N, or it is not compared at all.
- Its docstring states each tolerance with its reason, and which lower
  precision or broken path that limit refuses.  A limit read on the chip for
  one configuration goes into ``limits/<configuration>.json`` with its
  readings, and is applied on top of the module's own: it can only tighten.
"""
