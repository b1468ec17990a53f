"""Plain references, one module per architecture, found by the name a
configuration file gives under ``reference``.  Each provides ``Spec``
(``Spec.from_config``), ``logits(weights, spec, tokens, precision)``,
``weight_shapes(cfg)`` and ``program_kwargs(cfg)``."""
