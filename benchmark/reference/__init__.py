"""Plain float32 references: the models' published equations in straightforward
``jax.numpy``, independent of ``p2pfl_tpu.models`` and of every kernel. They
read the program's parameter trees by name only. Callers run them under
``jax.default_matmul_precision("highest")`` (a float32 matmul on a TPU is
otherwise computed in bf16 passes)."""
