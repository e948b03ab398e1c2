"""Plain references, one file a model family (a family's adapter under
benchmark/models/ names its file in ``REFERENCE``).

The contract of a reference module. It is the architecture's forward pass
(and loss) in straightforward ``jax.numpy``: float32 everywhere, every
matmul under ``jax.default_matmul_precision("highest")``, no kernel, no
cache, no batching trick. It imports nothing of ray_tpu: it takes the
parameter pytree as DATA, in the layout the program keeps it, made from
the seed by the caller. ``**kw`` below is what the adapter's
``reference_kwargs(cfg)`` returns (for the dense block ``theta``, ``eps``;
a negative control overrides one of them), the same for all four:

    hidden(params, tokens [B, T], **kw)
        -> hidden states before the final norm [B, T, D], float32
    logits_at(params, tokens [B, T], positions [P], **kw)
        -> logits [B, P, V] (logits at position p predict token p + 1)
    deficits(params, hidden_i [T, D], first, served [W], n, **kw)
        -> (largest reference max logit - reference logit of the served
           token over the n tokens served from position first + 1 on,
           whether every logit read is finite); ``first`` and ``n`` may be
           traced, ``served`` is padded to a fixed width
    loss(params, tokens [B, T + 1], **kw)
        -> mean next-token cross-entropy, a Python float

Memory is the reference's own matter (one layer cast to float32 at a
time, one sequence's scores at a time): checks.py calls it at the served
depth and the cell's longest sequences on the chip the replica left.
"""
