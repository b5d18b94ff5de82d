"""One module per model family: how a configuration becomes seeded
weights, batches, a work count and the program's compiled step."""
