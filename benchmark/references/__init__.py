"""Plain references: one module per architecture, named by the ``reference``
key of a configuration's file. Each is a straightforward forward pass written
against jax.numpy alone, independent of the program's lowering, scheduler and
cache; ``serve.py`` holds the served tokens to its logits."""
