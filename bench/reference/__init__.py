"""Plain references, one module per model family, named by the ``reference``
key of a configuration file. Each gives ``init(cfg, key)``, ``loss(params,
tokens, labels, cfg, math)`` and ``train_flops_per_token(cfg, seq,
n_params)``."""
