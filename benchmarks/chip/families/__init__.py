"""Model families of the chip benchmark, one module each.

A configuration names its family under ``"family"``;
``harness.load_cell`` loads ``families/<family>.py`` from the benchmark's
own directory, as it finds a traffic mix or a metric's reader, so a new
family arrives as a file. A family module provides:

- ``Dims``: a frozen, hashable dataclass of the model's shapes, with
  ``Dims.from_keys(name, keys)`` reading them from the configuration;
- ``repo_arch(dims, config)``: the repo ``ArchConfig`` that serves the
  model, checked against the configuration's keys (a ``ValueError`` where
  the repo would serve another function);
- ``served_params(dims, words)``: the program's weight tree drawn from the
  seed's words (:mod:`benchmarks.chip.weights`) in the type it serves, one
  jitted program with ``dims`` static; and ``layer_weights(dims, words,
  layer)``, one layer alone, equal to the same layer of the stacked draw;
- ``logits(dims, words, tokens, first, quant=None)``: the plain float32
  reference's logits ``[B, T - first, V]`` at positions ``first..T-1``,
  computed without the program; with ``quant`` (``precision.QUANTS``) the
  control, in a lower precision;
- ``prefill_flops(dims, batch, seq)``, ``decode_flops(dims, batch,
  cache_len)`` and ``decode_bytes(dims, batch, cache_len)``: the work a
  step needs, whatever implements it.

Every other module reaches the family through the dims it is given
(``weights.family_of``) and names none.
"""
