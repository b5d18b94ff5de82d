"""chipbench: the ledgered benchmark of paddle_tpu (see README.md)."""
