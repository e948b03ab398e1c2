"""The seam between a model architecture and the serving engine.

The engine (serve/llm/engine.py) and the cache manager (serve/llm/
kv_cache.py) never name an architecture: they reach it through the module
that defines the model configuration's class (:func:`block_of`). A module
that can be served provides, at module level:

    init_params(key, cfg), load_params(path, cfg)
    check_tp_divides(cfg, tp), serve_partition_rules()
    serve_params(params, cfg) -> params in the block's SERVED FORM
        ``init_params`` / ``load_params`` give the tree as a checkpoint
        lays it; the ``serve_*`` functions below read this form of it.
        Idempotent (served in, served out), the same leaves under names
        that tell the two forms apart, so that the engine applies it once
        as it takes its weights and every paged program of kv_cache.py
        once more on entry: nothing for the engine's tree, the conversion
        inside the program for a caller that hands it a checkpoint's.
        A block that keeps K and V a head of a whole lane tile holds each
        ``[in, H, hd]`` projection of its mixer HEAD-MAJOR
        (:func:`head_major`); one that has nothing to re-lay returns its
        tree.
    cache_spec(cfg) -> CacheSpec     what the cache manager must hold
    serve_layers(cfg) -> None | tuple[LayerDef, ...]
        None: every layer is the same, ``params["layers"]`` is one stacked
        pytree and the paged programs scan it. A tuple: layers differ,
        ``params["layers"]`` is a list, and the programs walk it in order.
    rope_freqs(cfg, positions)
    serve_embed(params, tokens, cfg) -> x
    serve_qkv(x, layer, cos, sin, cfg) -> q, k, v        (attention mixer)
    serve_attn_out(attn, layer) -> the mixer's output, before the residual
    serve_gated_qkv(x, layer, cos, sin, cfg, ld) -> q, k, v, gate
        (gated mixer: attention whose output is multiplied lane by lane by
        ``gate`` [B, T, H, D] before the output projection; it gets the
        layer's definition, so a layer may rotate or not by its window)
    serve_gated_out(attn, gate, layer, cfg) -> the mixer's output, before
        the residual (``attn`` and ``gate`` with or without the token axis,
        alike)
    serve_sink_qkv(x, layer, cos, sin, cfg, ld) -> q, k, v, sink
        (sink mixer: attention whose projections read the layer's
        definition, so that a layer kind may rotate by its own angles
        (``cos`` / ``sin`` are whatever ``rope_freqs`` gave) and keep its own
        number of KV heads, and whose softmax may hold a learned SINK:
        ``sink`` float32 [H], one logit a query head that joins the
        denominator and weighs no value, or None for a layer without; its
        output goes through ``serve_attn_out``)
    serve_latent(x, layer, cos, sin, cfg) -> q, entry    (latent mixer: its
        cache spec states ``latent_dim``) ``entry`` [B, T, latent_dim] is
        the ONE row a token leaves in the cache, and ``q`` [B, T, H,
        latent_dim] the query with the per-head key expansion absorbed
        into it, so that ``q . entry`` is the head's score against that
        token and the first ``value_dim`` lanes of the rows, weighted by
        the softmax, are what the head reads: multi-query attention with
        one KV head whose key rows hold their own values
    serve_latent_out(o, layer) -> the mixer's output, before the residual:
        ``o`` [B, T, H, value_dim] the weighted rows, mapped back to the
        heads' values and through the output projection
    serve_latent_expanded(x, layer, cos, sin, cfg) -> q, k, v, entry
        the same mixer with keys and values expanded per head (q and k
        [B, T, H, head_dim], v [B, T, H, any width]) for a whole prompt
        that attends to nothing cached; its output goes through
        ``serve_attn_out``. The two forms give the same numbers.
    serve_conv(x, layer, prev, cfg) -> (x + mixer, ext)  (conv mixer only:
        ``prev`` [B, K-1, D] the state the sequence's earlier tokens left,
        ``ext`` [B, K-1+T, D] that state followed by this call's columns,
        of which the manager keeps the last K-1 real ones)
    serve_hybrid_in(x, layer, cos, sin, cfg) -> (q, k, v), (z, xbc, dt)
        (hybrid mixer only: the layer's one norm and both mixers' inputs,
        ``xbc`` [B, T, C] BEFORE the convolution, whose last K-1 real
        columns the manager keeps; then ``serve_ssm_conv(ext, layer, cfg)
        -> x [B, T, H, P], b, c [B, T, G, N]`` over the kept columns and
        the call's, ``serve_ssm_step(dt, layer) -> dt, a`` (after bias and
        softplus, float32; the heads' negative rates), the manager's scan
        or update of the state (ops/ssm.py), ``serve_ssm_out(y, x, z,
        layer, cfg)`` and ``serve_attn_out(attn, layer, cfg)``: the two
        branches as the residual takes them)
    serve_ffn(x, layer, cfg, ld) -> (x + ffn, choice | None)
        ``choice`` int32 [rows, top_k]: the experts a routed layer took
    serve_final_norm(x, params, cfg), serve_lm_head(x, params, cfg)

A MIXER KIND (``LayerDef.mixer``; every layer of a block with
``serve_layers`` None is "attn") is ONE function in kv_cache.py's table
``_MIXERS``, ``mixer(x, kv, layer, ld, l, geometry, cfg) -> (x + mixer,
kv)``, which every program that reads the cache back runs through the one
layer body (``kv_cache._layer``: the mixer, then ``serve_ffn``); a program
states only its geometry (where this call's rows go, how it reads the cache
back) and no mixer names a program. "attn" (``serve_qkv``,
``serve_attn_out``) writes K and V a head, then reads the layer back;
"gated" (``serve_gated_qkv``, ``serve_gated_out``) is "attn" with a gate on
what it read; "sink" (``serve_sink_qkv``, ``serve_attn_out``) is "attn"
that is told its layer's definition and may hand the read a learned sink,
one more column of the softmax that weighs no value; "latent" (``serve_latent``, ``serve_latent_out``) writes the
one row and reads it back in the absorbed form; "conv" (``serve_conv``) keeps its state
in the row of the sequence's first page: a call that starts a sequence
reads zeros instead, and the row keeps the state as of the call's last real
column; "hybrid" (``serve_hybrid_in``, ``serve_attn_out`` with the
configuration, ``serve_ssm_conv``, ``serve_ssm_step``, ``serve_ssm_out``)
runs TWO mixers off one norm and adds both to the residual: the "attn"
write and read, and a state-space mixer whose row holds the convolution's
last columns and a recurrent state of a matrix a head (ops/ssm.py: a
chunked scan over a call's columns from the carried state, an update in
place for a call of one column a slot; columns past the real ones leave
the state as it was). A whole prefill reads nothing back (the prompt's own rows, "latent"
through ``serve_latent_expanded``, the write after the feed-forward) and
keeps a layer of its own. A new kind provides its ``serve_*`` functions
here, one function and one entry there, what it keeps in ``CacheSpec``
and, only if it reads the cache in a new way, a wrapper in ops/
paged_attention.py; it edits no program.

A WINDOW LAYER (``LayerDef.window`` W above 0, of a mixer that keeps K and V
a head; the cache spec states ``window`` and how many ``window_layers``):
query i sees key j iff ``0 <= i - j < W``. Such a layer's pages are a RING:
the manager keeps ``kv_cache.ring_pages`` pages a slot for it whatever the
context (the window, the widest call's span and a page), position p lies in
entry ``(p // page) % ring`` of the slot's ring table, and a page whose last
token has left every future query's window is written again. Window layers
have a pool and a table of their own (``kw`` / ``vw``, the tail of the page
table), which may hold another number of KV heads than the full layers'
(``CacheSpec.window_kv_heads``); full layers keep the growing table. Every paged read of a block
that has window layers runs the walking body with a lower edge (ops/
paged_attention.py), a full layer's edge 0. Prefix reuse, the kv tier,
speculation and disaggregated hand-off all assume that a page, once
written, holds its tokens for the sequence's life: the engine does none of
them for such a block (and counts).

``cfg.head_dim``, ``cfg.dtype`` and ``cfg.max_seq_len`` are read off the
configuration itself (``head_dim``: the width of a query and key head, so
the softmax scale is ``head_dim ** -0.5`` for a latent mixer too).

A block that generates by diffusion over blocks says so in its cache spec
(``block_length`` B above 1, ``mask_token``) and provides two more values on
its configuration, ``denoise_passes`` S and (derived) ``B / S`` positions
revealed a pass. For such a block positions are cut into blocks of B from
0; key j is visible to query i iff ``j // B <= i // B`` (every paged
program masks so); a prefill or a prefill chunk COMMITS the prompt's whole
blocks (K / V of positions below ``true_len - true_len % B``), its logits
mean nothing and it yields no token: the ``true_len % B`` tokens left over
start the slot's pending block, the rest of which holds ``mask_token``.
``kv_cache.paged_block_step`` then runs a pending block's B positions
against the cache, S times without keeping K / V (the engine reveals B / S
masked positions after each, by confidence) and once more, clean, to
commit it; the engine runs that last pass and the next block's first as
one (``kv_cache.paged_block_pair_step``). The logits AT a masked position are the distribution of the
token that belongs there. ``serve_lm_head`` of such a block is only called
on denoise passes.
"""

from __future__ import annotations

import dataclasses
import sys


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a block keeps between calls, a sequence.

    ``paged_layers`` layers write K and V of ``n_kv_heads`` x ``head_dim``
    a token into pages (with ``value_dim`` above 0 and no ``latent_dim``
    the VALUE rows are ``value_dim`` wide, not ``head_dim``: two pools of
    two widths, the key rows stored on whole 128-lane vectors); or, with
    ``latent_dim`` above 0, ONE row of
    ``latent_dim`` lanes a token (``n_kv_heads`` 1), of which the first
    ``value_dim`` are also the values: the pool is then one array, a
    latent (compressed) cache. ``state_layers`` layers keep state a
    SEQUENCE (not a token): state that survives between decode steps, is
    carried from one prefill chunk to the next and cannot be rebuilt from
    the pages. Either ONE array of ``state_shape`` in the activations'
    dtype, or each array that ``state_arrays`` states, ``((shape, dtype),
    ...)`` with "" for the activations' dtype (a convolution's last columns
    beside a recurrent state in float32: each its own array a layer, so a
    kernel may update one where it lies). ``state_per_slot``: false, the
    state pool has a row a PAGE and a sequence's row is its first page,
    whichever page that is; true, a row a SLOT: first pages come from a
    reserved range as long as there are slots (``PageAllocator``'s
    ``first_pages``), the pool holds that many rows and the trash row, and
    a sequence's row is still its first page (a state of megabytes a row
    cannot be held a page). ``routed_layers`` layers choose ``top_k`` of
    ``n_experts`` experts a token, and the programs record the choice
    (``n_experts``: the experts whose rows THIS replica multiplies, the
    first of the router's where it holds a share: what the engine's counts
    of experts touched run over).
    ``window_layers`` more layers write K and V of the same widths into a
    ring of pages a slot and see the last ``window`` tokens only (the
    module docstring's "a window layer"), of ``window_kv_heads`` KV heads
    (0: ``n_kv_heads``, as the full layers).
    ``block_length``: 1 = a step yields a token a sequence; B above 1 = the
    block generates by diffusion over blocks of B positions, of which a
    not yet revealed one holds ``mask_token`` (never produced)."""
    paged_layers: int
    n_kv_heads: int
    head_dim: int
    state_layers: int = 0
    state_shape: tuple = ()
    state_arrays: tuple = ()
    state_per_slot: bool = False
    routed_layers: int = 0
    top_k: int = 0
    n_experts: int = 0
    block_length: int = 1
    mask_token: int = -1
    latent_dim: int = 0
    value_dim: int = 0
    window: int = 0
    window_layers: int = 0
    window_kv_heads: int = 0


@dataclasses.dataclass(frozen=True)
class LayerDef:
    """One layer of a block whose layers differ: its mixer ("attn" |
    "gated" | "sink" | "conv" | "latent" | "hybrid": a key of kv_cache.py's
    ``_MIXERS``, the module docstring's "a mixer kind"), its feed-forward
    kind ("dense" | "routed"), which row of the pool, of the slot state and
    of the routing record is its own, its window (0: a full layer, whose
    row is the growing pool's; above 0: a window layer, whose row is the
    ring pool's), and whether its softmax holds a learned sink (a "sink"
    mixer's ``serve_sink_qkv`` then returns one)."""
    mixer: str
    ffn: str
    page_layer: int = -1
    state_layer: int = -1
    routed_layer: int = -1
    window: int = 0
    sink: bool = False


HEAD_MAJOR = "_hm"


def head_major(attn: dict, names) -> dict:
    """``attn`` with each projection of ``names`` (``[.., in, H, hd]``,
    stacked or not) held head-major, ``[.., H, in, hd]``, under its name +
    ``HEAD_MAJOR``: what ``einsum("btd,hdk->bthk")`` reads in place. On a
    v5e the compiler reads ``[in, H, hd]`` under ``"btd,dhk->bthk"``
    through a copy with the heads outermost, every execution, or every
    layer of every step of a scanned stack (PERF.md section 6, PR 54). A
    name that is not there is already served."""
    import jax.numpy as jnp
    out = dict(attn)
    for name in names:
        if name in out:
            out[name + HEAD_MAJOR] = jnp.swapaxes(out.pop(name), -3, -2)
    return out


def head_major_nbytes(params) -> dict:
    """{leaf name: bytes over the layers} of the leaves that
    :func:`head_major` made (the engine's ``weights_head_major``)."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = str(getattr(path[-1], "key", ""))
        if name.endswith(HEAD_MAJOR):
            out[name] = out.get(name, 0) + int(leaf.nbytes)
    return out


def gqa_expand(k, n_rep):
    """k [B, T, Hkv, D] -> [B, T, Hkv * n_rep, D], kv-major: query head
    h reads KV head h // n_rep."""
    if n_rep == 1:
        return k
    import jax.numpy as jnp
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d)).reshape(
        b, t, h * n_rep, d)


def block_of(cfg):
    """The module that defines ``cfg``'s class: the architecture's block."""
    return sys.modules[type(cfg).__module__]
