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

A family with routed experts (its adapter provides ``routing_taken``; the
adapter's contract, with ``paged_programs`` and ``attn_layers``, is in the
docstring of the adapter that benchmark/models/ has).
A choice of k experts out of n is not continuous in the arithmetic: the
program's bfloat16 hidden state and this module's float32 one differ by
about 1 % of a logit's spread, a near-tie between the k-th and the next
score turns over, and a swapped expert moves the logits by many times any
tolerance. So check 1 reads the experts the program chose after every
call and hands them to the reference, which also says how far from its
own choice they lie:

    logits_at(params, tokens [1, T], positions, routing=choice, **kw)
        ``choice`` int32 [L_r, T, k]: for each layer that routes (in
        order) and each position, the experts to evaluate. The combine
        weights are the reference's OWN float32 scores of those experts
        by the published rule (normalisation, scaling factor). Without
        ``routing=`` the reference routes by its own scores (``hidden``
        takes the keyword too; check 2 does not pass it).
    routing_slack(params, tokens [1, T], routing, **kw)
        -> float32 [L_r, T]: per decision the reference's k-th largest
           selection score (bias included where the model selects under
           one) minus the smallest selection score among the experts
           named, on the hidden states that taking the named experts
           gives: 0 when the program chose the reference's top k, ``inf``
           for an expert named twice or not there.

Check 1 then holds four numbers of such a family to four limits of
``checks.logits`` in the configuration's file, each with a ``_why`` from
measurement at size (beside ``checks.logits.backend`` and its
``backend_why`` where the block cannot take the Pallas kernel), every
logit finite besides:
  ``max_abs_err <= tolerance`` under the forced choice: as tight as a dense
      family's (largest seen x 1.5) and for the same faults, a rule left
      out or wrong. It does NOT see a lower precision of the experts: the
      maximum over a million logits swings by a quarter from seed to seed,
      and int8 experts raise it by no more.
  ``rms_err <= rms_tolerance``: the root-mean-square error over the compared
      logits, which is the final hidden state's error and steady from
      seed to seed (+-5 % at width 2048). bfloat16 activations and an int8
      grid on the experts are each about 1 % of the logits' spread and add
      in squares, so int8 experts raise it by 1.27-1.30 x on a per-tensor
      grid, on every seed alike: the limit goes between the sound runs'
      largest and that control's smallest, some six standard deviations
      from either, NOT the factor of three a widest gap would need. A
      per-column int8 grid raises it by 1.12-1.14 x, which no fixed limit
      separates from the sound runs' own spread: that step is not seen
      (PERF.md section 7 says what could see it). Read both sets at the
      configuration's own size on a dozen seeds or more; a smaller width
      spreads wider than the effect.
  ``max slack <= routing_slack`` and the share of decisions with slack
      above 0 at most ``routing_flip_share_max``.
``model_config(sz, n_layers=depth)`` has to give a depth that holds every
kind of layer the model has.

What check 2 can show for such a family. Served tokens come over HTTP
without the choices behind them, so they are teacher-forced through the
reference's own routing. A decision the replica turned over moves the
distribution its token was the best of, so ``margin`` has to be wider than
a dense family's 0.25 (measure it at size), and the check then guards the
tokens (a replaced token misses by 3-4), not the precision: that is
check 1's ``rms_tolerance``. The train check (first loss against ``loss``)
is as it was; a routed train cell needs its own reckoning of what a
turned-over choice does to the first loss.

Memory is the reference's own matter (one layer cast to float32 at a
time, one sequence's scores at a time): checks.py calls it at the served
depth and the cell's longest sequences on the chip the replica left.
"""
