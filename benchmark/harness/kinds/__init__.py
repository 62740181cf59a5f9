"""One module per configuration ``kind``: the only place where the
harness branches on what a model is."""
