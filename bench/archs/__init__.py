"""One module per architecture: ``bench/archs/<arch>.py``.

A configuration file names its architecture by the key ``"arch"``; without
it the architecture is ``dense``.  :func:`bench.registry.arch` finds the
module by that name, so a new architecture enters the benchmark as files
alone: its module, its configuration, and a cell's workload and traffic
files.  The harness reaches the model, its weights, its reference and its
counts only through the module, which provides:

* ``model_config(c) -> ModelConfig``: the program's configuration for the
  file ``c``; it raises on a key of the file it does not model.  It alone
  reaches the program, importing :mod:`bench.system` when called, so that
  loading the module and running its reference import nothing of it;
* ``last_logits(weights, c, tokens, lengths, *, quant=None) -> ndarray
  [n, V]``: the plain reference forward (float32, ``highest`` matmul
  precision, built from :mod:`bench.reference` and importing nothing of
  the program), the next-token logits after each row's ``lengths[i]`` of
  its ``tokens`` ``[n, S]``; ``quant="fp8"`` is the calibration control
  (``bench/calibrate.py``);
* ``window_flops(c, stats, *, wave, max_len) -> float``: model FLOPs of the
  measured window (``step_mfu``), from the window's ``ServeStats`` deltas
  ``stats``, so that a count the program keeps (tokens routed to held
  experts, say) can take the place of an expectation;
* ``attention_kv_bytes(c, stats) -> int``: the key and value bytes the
  ``decode_attention`` kernel had to read over the window
  (``decode_attention_roofline``); 0 where ``stats`` holds no count of
  them;
* optionally ``make_weights(cfg, seed)``: the weights in the program's
  parameter tree, made on the device from the seed; without it
  :func:`bench.system.make_weights`.
"""
