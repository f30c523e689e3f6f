"""Plain references, one module per architecture.

A configuration file names its module under ``"reference"`` as a path
under ``bench/reference/`` (``"bench/reference/dense_gqa.py"``);
``bench.lib.program.reference`` imports it. Everything the harness does
that depends on the architecture goes through that module, so that a new
architecture is added as a configuration file and a module of its own,
with no edit to the harness.

A module provides, with ``model`` the configuration file's ``"model"``
object and ``cfg`` the program's ``ModelConfig``:

Weights (float32, in the module's own layout):

- ``seed_key(seed)``: a PRNG key from any integer seed;
- ``shapes(model)``: the layout's tree of weight shapes (tuples);
- ``init(key, model)``: the weights from the key, traceable under ``jit``;
- ``init_weights(model, seed)``: the same weights, on the device, from
  one jitted call.

Layout:

- ``to_program_params(w, cfg)``: a tree in the module's layout (weights,
  gradients, norms) mapped onto the program's parameter tree. It raises
  where ``cfg`` is a model that the layout cannot express.

Correctness:

- ``logits_at(w, model, tokens, start, n, quant=None)``: float32 logits
  ``(n, V)`` at positions ``[start, start + n)`` of one padded sequence;
  ``quant="fp8"`` gives the lower-precision control;
- ``train_step(w, mu, nu, tokens, labels, step, *, model, opt, quant=None)``:
  one AdamW step of the mean loss, returning ``(w, mu, nu, loss, norms)``
  with the per-leaf norms of the clipped gradient. Optional: only a
  configuration that a train cell uses needs it.

Counts (operations count a multiply-add as two):

- ``param_count(model)``;
- ``forward_flops(model, start, stop)``: the tokens at ``[start, stop)``
  of one sequence whose earlier positions are cached;
- ``train_flops_per_token(model, seq_len)``;
- ``decode_step_flops(model, positions)``;
- ``decode_step_bytes(model, positions, bytes_per_weight, bytes_per_kv_value=2)``.

The counts are what ``serve.decode_roofline``, ``serve.step_mfu`` and
``train.mfu`` divide by, so each module counts its own architecture's
least work: a latent cache's bytes, the experts a step touched, not the
dense formula. ``bench/lib/flops.py`` holds the dense formulas and the
generic helpers (``attended``, ``attended_sum``) any module may use.
"""
