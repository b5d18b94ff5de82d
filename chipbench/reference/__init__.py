"""The plain references: each model's loss in straightforward
`jax.numpy`, float32 at `highest` matmul precision, with AdamW beside
them. Nothing here imports `paddle_tpu` or anything it made."""
