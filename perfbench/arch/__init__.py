"""What the benchmark knows of each architecture, one module per
``model_type``.

A configuration file names its ``model_type``; ``bench.arch(cfg, root)``
loads ``perfbench/arch/<model_type>.py`` from the checkout, and a
configuration whose module is missing is refused, never given a default.  So
an architecture enters the benchmark as files: its module, its configuration,
its traffic and check files, and entries in ``BENCHMARK.json``.

A module gives everything that depends on the architecture, and nothing
else.  ``cfg`` is the configuration file as loaded (Hugging Face key names,
plus whatever else the module reads from it, such as ``layer_types``, a
published expert count beside the count held here, or multipliers):

``program_config(cfg)``
    The program's ``ModelConfig`` (``repro.configs.base``), with any
    ``MoEConfig``, ``SSMConfig`` or other field the program has.
``weight_shapes(cfg)``
    ``{path: shape}`` of every leaf of the tree the program serves, paths
    joined with ``/``.  ``weights.make`` draws them from the seed in one
    jitted call: leaves whose path ends in ``scale`` around 1, the others
    normal with the deviation of
``weight_std(path, shape, cfg)``
    a float.
``reference_logits(cfg, params, tokens, rows, control, scales)``
    The plain float32 forward pass (``perfbench/reference.py`` gives the
    generic parts: ``Matmul``, ``rms_norm``, ``rope``, ``attention``).
    ``params`` is the weight tree flattened to ``{path: leaf}``, ``tokens``
    the sequence padded to a multiple of ``reference.PAD_TO``, ``rows`` the
    positions whose logits are returned, ``control`` ``None``, ``"int8"`` or
    ``"fp8"``, and ``scales`` the per-tensor int8 scales by path (empty but
    for ``"int8"``), which ``Matmul.weight`` takes.  It imports nothing of
    the program.
``prefill_flops(cfg, n)``, ``decode_flops(cfg, contexts)``
    Model operations of a prefill of ``n`` prompt tokens, and of one decode
    step over sequences whose attention spans are ``contexts``.
``flash_calls(cfg, s)``
    ``(operations, bytes)`` of each flash-attention call of one prefill at
    bucket ``s``, one entry per call: a layer with no attention makes none,
    a windowed one counts its window.  A kernel that a module's model adds
    has its counts in the module too.
"""
