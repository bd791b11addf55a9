"""Sharding rules: parameter/optimizer/batch/cache PartitionSpecs.

Strategy (DESIGN.md §5): batch over ("pod","data"); TP over "model"
(heads / d_ff / vocab / experts); FSDP (ZeRO-3 style) over "data"
[+"pod"] on each weight's non-TP matrix dim. Rules are regex → spec-
builder over the flattened param path; stacked layer leaves (under
``layers``/``enc_layers``) get a leading None for the scan dim.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axes(mesh: Mesh):
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names)
    if len(batch) == 1:
        # Bare name, not a 1-tuple: PartitionSpec treats P(("data",)) and
        # P("data") as distinct specs, and consumers compare against the
        # bare-name form.
        batch = batch[0]
    fsdp = batch  # ZeRO across pods too
    model = "model" if "model" in names else None
    return batch or None, (fsdp or None), model


# rule table: regex on ".../leaf" path → f(batch, fsdp, model) → P(...)
_RULES: list[tuple[str, Any]] = [
    # embeddings / unembedding
    (r"embed/table$",            lambda b, f, m: P(m, f)),
    (r"unembed/w$",              lambda b, f, m: P(f, m)),
    # attention
    (r"attn.*/w[qkv]$",          lambda b, f, m: P(f, m)),
    (r"attn.*/wo$",              lambda b, f, m: P(m, f)),
    (r"(q|k)_norm/scale$",       lambda b, f, m: P()),
    # dense mlp / shared expert
    (r"(mlp|shared)/w_(gate|up)$", lambda b, f, m: P(f, m)),
    (r"(mlp|shared)/w_down$",    lambda b, f, m: P(m, f)),
    (r"mlp/b_up$",               lambda b, f, m: P(m)),
    (r"mlp/b_down$",             lambda b, f, m: P()),
    # MoE experts: EP over model when E divides it, else TP over moe_d_ff
    # (shape-aware — see _spec_for_path special case below)
    (r"moe/router$",             lambda b, f, m: P(f, None)),
    # mamba2
    (r"mamba/w_in$",             lambda b, f, m: P(f, m)),
    (r"mamba/w_out$",            lambda b, f, m: P(m, f)),
    (r"mamba/conv_[wb]$",        lambda b, f, m: P(None, m) if True else P()),
    (r"mamba/norm_scale$",       lambda b, f, m: P(m)),
    (r"mamba/(a_log|dt_bias|d_skip)$", lambda b, f, m: P()),
    # rwkv6
    (r"tm_cm/w_[rkvg]$",         lambda b, f, m: P(f, m)),
    (r"tm_cm/w_o$",              lambda b, f, m: P(m, f)),
    (r"tm_cm/cm_[kr]$",          lambda b, f, m: P(f, m)),
    (r"tm_cm/cm_v$",             lambda b, f, m: P(m, f)),
    (r"tm_cm/w_lora_a$",         lambda b, f, m: P(f, None)),
    (r"tm_cm/w_lora_b$",         lambda b, f, m: P(None, f)),
    (r"tm_cm/(mu_.|cm_mu|w0|u_bonus|ln_scale|ln_bias)$", lambda b, f, m: P()),
    # norms & anything 1-D
    (r"(ln\d?|ln_x|final_norm|enc_final_norm)/(scale|bias)$", lambda b, f, m: P()),
]


def _spec_for_path(path: str, shape: tuple, mesh: Mesh) -> P:
    ndim = len(shape)
    b, f, m = _axes(mesh)
    n_model = dict(mesh.shape).get("model", 1)
    stacked = path.startswith(("layers/", "enc_layers/")) or "/layers/" in path
    if re.search(r"moe/w_(gate|up|down)$", path):
        # stacked leaf: [L, E, d, f] / [L, E, f, d]
        e = shape[1] if stacked else shape[0]
        if m and e % n_model == 0:
            spec = P(m, f, None) if path.endswith(("gate", "up")) else P(m, None, f)
        else:  # EP impossible → replicate experts, TP the ffn dim
            spec = P(None, f, m) if path.endswith(("gate", "up")) else P(None, m, f)
        parts = ([None] if stacked else []) + list(spec)
        return P(*parts)
    for pat, fn in _RULES:
        if re.search(pat, path):
            spec = fn(b, f, m)
            break
    else:
        spec = P()
    parts = list(spec)
    # pad/truncate to tensor rank (minus stack dim)
    want = ndim - (1 if stacked else 0)
    parts = (parts + [None] * want)[:want]
    if stacked:
        parts = [None] + parts
    return _validate(P(*parts), shape, mesh)


def _validate(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop axes whose mesh size doesn't divide the dim (e.g. odd vocabs:
    whisper 51865, internvl 151655 — those fall back to replicated on that
    dim; FSDP/TP still applies to the other dims)."""
    sizes = dict(mesh.shape)
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        prod = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            prod *= sizes.get(a, 1)
        out.append(ax if dim % prod == 0 else None)
    return P(*out)


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


def param_specs(params_shape, mesh: Mesh):
    """Pytree of PartitionSpec matching a params (or shape) pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for_path(_path_str(path), tuple(leaf.shape), mesh),
        params_shape,
    )


def opt_state_specs(opt_shape, params_spec, mesh: Mesh):
    """m/v/master shard exactly like their parameter; step replicated."""
    return {
        "m": params_spec, "v": params_spec, "master": params_spec,
        "step": P(),
    }


def batch_specs(batch_shape, mesh: Mesh):
    """Token batches: batch dim over ("pod","data") when divisible."""
    b, f, m = _axes(mesh)
    n_batch = 1
    if b:
        for ax in (b if isinstance(b, tuple) else (b,)):
            n_batch *= mesh.shape[ax]

    def spec(path, leaf):
        if len(leaf.shape) == 0:
            return P()
        bdim = leaf.shape[0]
        first = b if b and bdim % max(n_batch, 1) == 0 and bdim >= n_batch else None
        rest = [None] * (len(leaf.shape) - 1)
        # embeddings streams ([B, S, d_model] stubs) put d_model on model
        if len(leaf.shape) == 3 and path.endswith(("frames", "patches")):
            rest = [None, None]
        return P(first, *rest)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec(_path_str(path), leaf), batch_shape)


def cache_specs_tree(cache_shape, mesh: Mesh):
    """Decode caches: batch over DP axes when divisible, else shard the
    sequence axis (long_500k, B=1); heads over model."""
    b, f, m = _axes(mesh)
    n_batch = 1
    if b:
        for ax in (b if isinstance(b, tuple) else (b,)):
            n_batch *= mesh.shape[ax]

    n_model = dict(mesh.shape).get("model", 1)

    def spec(path, leaf):
        shape = leaf.shape
        nd = len(shape)
        if nd == 0:
            return P()
        leaf_name = path.split("/")[-1]
        # layout: [L, B, ...] (stacked caches)
        batch_ok = nd >= 2 and shape[1] % max(n_batch, 1) == 0 and shape[1] >= n_batch
        parts = [None] * nd
        if batch_ok:
            parts[1] = b
        if leaf_name in ("ssm", "wkv"):
            # [L, B, H, N, P] / [L, B, H, k, k]
            if nd == 5 and m and shape[2] % n_model == 0:
                parts[2] = m
        elif leaf_name == "conv":
            if nd == 4 and m and shape[3] % n_model == 0:
                parts[3] = m
        elif leaf_name in ("tm_shift", "cm_shift"):
            if nd == 3 and m and shape[2] % n_model == 0:
                parts[2] = m
        elif nd == 5:
            # attention caches [L, B, Hkv, S, hd]: TP on heads when they
            # divide; otherwise sequence-parallel the cache over "model".
            if m and shape[2] % n_model == 0:
                parts[2] = m
            elif m and shape[3] % n_model == 0:
                parts[3] = m
            if not batch_ok and b and shape[3] % n_batch == 0 and parts[3] is None:
                parts[3] = b           # long-context B=1: SP over DP axes too
        return P(*parts)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec(_path_str(path), leaf), cache_shape)


def to_named(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def spmd_epoch_specs(axis_name: str = "data"):
    """(in_specs, out_specs) for the analytics SPMD epoch under
    ``shard_map`` (``repro.api.compile(spec, mesh=...)``): the key is
    replicated and the epoch's ``[T, fanin[0], ...]`` node rows
    (values, strata, counts) are split over ``axis_name`` on the node
    axis; the root's (sum, mean) results are replicated (every device
    computes the root stage redundantly)."""
    rows = P(None, axis_name)
    return (P(), rows, rows, rows), (P(), P())


def spmd_query_epoch_specs(axis_name: str, qstate):
    """Sketch-aware ``shard_map`` spec components for the SPMD query
    plane (``repro.api.spmd`` tenant lowering).

    Per-device sketch state leaves carry a leading device axis sharded
    over ``axis_name`` (each device owns exactly its own quantile
    buffers / CM tables / top-k slots — they all-gather as O(sketch)
    summaries at the window boundary, never as items); the epoch's node
    rows are split on their node axis; everything the root returns
    (per-window answers, bounds, built-in workload) is replicated.
    Returns ``dict(qstate=..., rows=..., replicated=P())`` — components,
    because the caller owns the state/output pytree structure they
    assemble into."""
    return dict(
        qstate=jax.tree.map(lambda _: P(axis_name), qstate),
        rows=P(None, axis_name),
        replicated=P(),
    )
