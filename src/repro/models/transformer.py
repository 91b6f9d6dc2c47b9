"""The model stack: params metadata -> init/abstract/pspecs, and the three
execution modes (train forward, prefill, decode) over scanned blocks.

Parameters are stacked per block-pattern position (leading n_blocks dim) and
consumed with ``lax.scan`` so HLO size -- and 512-device compile time -- stays
flat in depth.  Every leaf carries logical sharding tags (layers.PD) from
which `param_pspecs` derives PartitionSpecs; there is exactly one source of
truth for shapes/sharding/init.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import LayerSpec, ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.sharding.specs import to_pspec

# ---------------------------------------------------------------------------
# parameter metadata
# ---------------------------------------------------------------------------

def _add_norm(cfg, d: dict, name: str):
    d[name] = L.PD((cfg.d_model,), (None,))
    if cfg.norm == "layernorm":
        d[name + "_b"] = L.PD((cfg.d_model,), (None,))


def _layer_defs(cfg: ModelConfig, spec: LayerSpec) -> dict:
    d = {}
    _add_norm(cfg, d, "ln1")
    if spec.mixer == "attn":
        d["attn"] = L.attn_defs(cfg)
    elif spec.mixer == "mla":
        d["attn"] = MLA.mla_defs(cfg)
    elif spec.mixer == "mamba":
        d["attn"] = M.mamba_defs(cfg)
    else:
        raise ValueError(spec.mixer)
    if cfg.post_block_norm:
        _add_norm(cfg, d, "ln1_post")
    if spec.cross_attn:
        _add_norm(cfg, d, "ln_x")
        d["xattn"] = L.attn_defs(cfg)
    if spec.mlp != "none":
        _add_norm(cfg, d, "ln2")
        if spec.mlp == "dense":
            d["mlp"] = L.mlp_defs(cfg)
        elif spec.mlp == "moe":
            d["mlp"] = MOE.moe_defs(cfg)
        else:
            raise ValueError(spec.mlp)
        if cfg.post_block_norm:
            _add_norm(cfg, d, "ln2_post")
    return d


def _stack(defs: dict, n: int) -> dict:
    return jax.tree.map(
        lambda pd: L.PD((n,) + pd.shape, (None,) + pd.axes, pd.fan_in),
        defs, is_leaf=lambda x: isinstance(x, L.PD))


def model_defs(cfg: ModelConfig) -> dict:
    d_model, v = cfg.d_model, cfg.padded_vocab
    if cfg.embed_shard == "dmodel":
        # collective-free embedding gather; invalid for tied embeddings
        # (the unembed contraction would need a full-vocab all-reduce)
        assert not cfg.tie_embeddings, "embed_shard=dmodel requires untied"
        embed_pd = L.PD((v, d_model), (None, "tp"), d_model)
    else:
        embed_pd = L.PD((v, d_model), ("tp", None), d_model)
    defs = {
        "embed": embed_pd,
        "final_norm": L.PD((d_model,), (None,)),
        "blocks": _stack(
            {f"L{i}": _layer_defs(cfg, s) for i, s in enumerate(cfg.pattern)},
            cfg.n_blocks),
    }
    if cfg.norm == "layernorm":
        defs["final_norm_b"] = L.PD((d_model,), (None,))
    if not cfg.tie_embeddings:
        defs["unembed"] = L.PD((d_model, v), ("fsdp", "tp"), d_model)
    if cfg.enc_layers:
        enc_spec = LayerSpec(mixer="attn", mlp="dense", encoder=True)
        defs["enc"] = {
            "pos": L.PD((cfg.enc_ctx, d_model), (None, None), d_model),
            "final_norm": L.PD((d_model,), (None,)),
            "blocks": _stack({"L0": _layer_defs(cfg, enc_spec)},
                             cfg.enc_layers),
        }
        if cfg.norm == "layernorm":
            defs["enc"]["final_norm_b"] = L.PD((d_model,), (None,))
    return defs


def _init_leaf(path: str, pd: L.PD, key, dtype):
    name = path.split("/")[-1]
    if "a_log" in name:
        ds = pd.shape[-1]
        base = jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))
        return jnp.broadcast_to(base, pd.shape).astype(dtype)
    if "d_skip" in name:
        return jnp.ones(pd.shape, dtype)
    if "dt_b" in name:
        return jnp.full(pd.shape, -4.6, dtype)  # softplus^-1(0.01)
    if pd.fan_in == 0 or name.startswith(("ln", "norm")) or name.endswith("_b") \
            or name.startswith(("b", "conv_b", "q_norm", "kv_norm")):
        return jnp.zeros(pd.shape, dtype)
    scale = 1.0 / math.sqrt(max(pd.fan_in, 1))
    return (jax.random.normal(key, pd.shape, jnp.float32) * scale).astype(dtype)


def _flatten_with_path(tree):
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, L.PD))[0]
    return [("/".join(str(getattr(k, "key", k)) for k in path), pd)
            for path, pd in flat]


def init_params(cfg: ModelConfig, key) -> dict:
    defs = model_defs(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    flat = _flatten_with_path(defs)
    keys = jax.random.split(key, len(flat))
    leaves = [_init_leaf(p, pd, k, dtype) for (p, pd), k in zip(flat, keys)]
    treedef = jax.tree_util.tree_structure(
        defs, is_leaf=lambda x: isinstance(x, L.PD))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def abstract_params(cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, dtype),
                        model_defs(cfg),
                        is_leaf=lambda x: isinstance(x, L.PD))


def param_pspecs(cfg: ModelConfig, axis_names) -> dict:
    return jax.tree.map(lambda pd: to_pspec(pd.axes, axis_names),
                        model_defs(cfg),
                        is_leaf=lambda x: isinstance(x, L.PD))


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _constrain(x, mesh, spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, to_pspec(spec, mesh.axis_names)))


def embed_tokens(cfg, params, tokens, mesh=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(_cdt(cfg))
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), _cdt(cfg))
    return _constrain(x, mesh, ("dp", None, None))


def _norm(cfg, lp, key, x):
    return L.norm_apply(cfg, lp[key], x, lp.get(key + "_b"))


def _moe_call(cfg, mp, x, mesh):
    if mesh is None:
        return MOE.moe_ref(cfg, mp, x)
    dp_total = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_total *= mesh.shape[a]
    batch_tag = "dp" if x.shape[0] % dp_total == 0 else None
    x_spec = to_pspec((batch_tag, None, None), mesh.axis_names)
    p_specs = jax.tree.map(
        lambda pd: to_pspec(pd.axes, mesh.axis_names),
        MOE.moe_defs(cfg), is_leaf=lambda v: isinstance(v, L.PD))
    fn = shard_map(
        functools.partial(MOE.moe_apply_local, cfg, axis="model"),
        mesh=mesh, in_specs=(p_specs, x_spec), out_specs=x_spec,
        check_vma=False)
    return fn(mp, x)


def _apply_layer(cfg, spec: LayerSpec, lp, x, positions, *, mesh,
                 mode="train", cache=None, kv_len=None, enc_out=None):
    """One layer; returns (x, new_cache_entry)."""
    new_cache = {}
    h = _norm(cfg, lp, "ln1", x)
    if spec.mixer == "attn":
        if mode == "decode":
            y, kv = L.attn_apply(cfg, lp["attn"], h, positions, spec=spec,
                                 cache=(cache["k"], cache["v"]), kv_len=kv_len)
            new_cache |= {"k": kv[0], "v": kv[1]}
        else:
            y, _ = L.attn_apply(cfg, lp["attn"], h, positions, spec=spec,
                                mesh=mesh)
            if mode == "prefill":
                k, v, mx = _fresh_kv(cfg, lp["attn"], h, positions, kv_len)
                new_cache |= {"k": k, "v": v}
    elif spec.mixer == "mla":
        if mode == "decode":
            y, kv = MLA.mla_apply(cfg, lp["attn"], h, positions,
                                  cache=(cache["ckv"], cache["kr"]),
                                  kv_len=kv_len)
            new_cache |= {"ckv": kv[0], "kr": kv[1]}
        else:
            y, _ = MLA.mla_apply(cfg, lp["attn"], h, positions, mesh=mesh)
            if mode == "prefill":
                ckv, kr = MLA._latents(cfg, lp["attn"], h, positions)
                new_cache |= {"ckv": _pad_cache(ckv, kv_len),
                              "kr": _pad_cache(kr, kv_len)}
    elif spec.mixer == "mamba":
        st = (cache["conv"], cache["h"]) if mode == "decode" else None
        y, st_new = M.mamba_apply(cfg, lp["attn"], h, state=st, mesh=mesh)
        if mode in ("decode", "prefill"):
            new_cache |= {"conv": st_new[0], "h": st_new[1]}
    if cfg.post_block_norm:
        y = _norm(cfg, lp, "ln1_post", y)
    x = x + y

    if spec.cross_attn:
        h = _norm(cfg, lp, "ln_x", x)
        if mode == "decode":
            kv = (cache["xk"], cache["xv"])
            new_cache |= {"xk": cache["xk"], "xv": cache["xv"]}  # read-only
        else:
            kv = _cross_kv(cfg, lp["xattn"], enc_out)
            if mode == "prefill":
                new_cache |= {"xk": kv[0], "xv": kv[1]}
        y, _ = L.attn_apply(cfg, lp["xattn"], h, positions, spec=spec,
                            kv_override=kv, mesh=mesh)
        x = x + y

    if spec.mlp != "none":
        h = _norm(cfg, lp, "ln2", x)
        if spec.mlp == "dense":
            y = L.mlp_apply(cfg, lp["mlp"], h)
        else:
            y = _moe_call(cfg, lp["mlp"], h, mesh)
        if cfg.post_block_norm:
            y = _norm(cfg, lp, "ln2_post", y)
        x = x + y
    return x, new_cache


def _fresh_kv(cfg, p, h, positions, max_len):
    cd = h.dtype
    b, s, _ = h.shape
    kv_n, hd = cfg.n_kv_heads, cfg.head_dim
    k = (h @ p["wk"].astype(cd))
    v = (h @ p["wv"].astype(cd))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    k = k.reshape(b, s, kv_n, hd)
    v = v.reshape(b, s, kv_n, hd)
    k = L.apply_rope(cfg, k, positions)
    return _pad_cache(k, max_len), _pad_cache(v, max_len), max_len


def _pad_cache(arr, max_len):
    """Pad (B, S, ...) to (B, max_len, ...) for the decode cache buffers."""
    s = arr.shape[1]
    pad = [(0, 0)] * arr.ndim
    pad[1] = (0, max_len - s)
    return jnp.pad(arr, pad)


def _cross_kv(cfg, p, enc_out):
    cd = enc_out.dtype
    b, s, _ = enc_out.shape
    k = (enc_out @ p["wk"].astype(cd)).reshape(b, s, cfg.n_kv_heads,
                                               cfg.head_dim)
    v = (enc_out @ p["wv"].astype(cd)).reshape(b, s, cfg.n_kv_heads,
                                               cfg.head_dim)
    return k, v


def _run_blocks(cfg, params, x, positions, *, mesh, mode="train",
                cache_blocks=None, kv_len=None, enc_out=None,
                pattern=None, remat=None):
    pattern = pattern or cfg.pattern

    res_spec = ("dp", "sp" if (cfg.seq_parallel and mode == "train")
                else None, None)

    def block_fn(x, bp, bc):
        entries = {}
        for i, spec in enumerate(pattern):
            x, e = _apply_layer(
                cfg, spec, bp[f"L{i}"], x, positions, mesh=mesh, mode=mode,
                cache=None if bc is None else bc[f"L{i}"], kv_len=kv_len,
                enc_out=enc_out)
            entries[f"L{i}"] = e
        return _constrain(x, mesh, res_spec), entries

    if remat if remat is not None else (cfg.remat and mode == "train"):
        block_fn = jax.checkpoint(block_fn)

    if cache_blocks is None:
        def body(c, bp):
            y, e = block_fn(c, bp, None)
            return y, e if mode == "prefill" else None
        x, entries = jax.lax.scan(body, x, params)
    else:
        def body(c, inp):
            bp, bc = inp
            return block_fn(c, bp, bc)
        x, entries = jax.lax.scan(body, x, (params, cache_blocks))
    return x, entries


def _positions_default(cfg, tokens):
    b, s = tokens.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (b, s, 3))
    return pos


def encode(cfg, params, frames, mesh=None):
    """Whisper encoder over precomputed (stub) frame embeddings (B, T, D)."""
    x = frames.astype(_cdt(cfg))
    t = x.shape[1]
    x = x + params["enc"]["pos"][:t][None].astype(x.dtype)
    x = _constrain(x, mesh, ("dp", None, None))
    pos = _positions_default(cfg, x[..., 0])
    enc_pat = (LayerSpec(mixer="attn", mlp="dense", encoder=True),)
    x, _ = _run_blocks(cfg, params["enc"]["blocks"], x, pos, mesh=mesh,
                       pattern=enc_pat)
    return L.norm_apply(cfg, params["enc"]["final_norm"], x,
                        params["enc"].get("final_norm_b"))


def forward_hidden(cfg, params, tokens, *, positions=None, extra_embeds=None,
                   enc_frames=None, mesh=None, remat=None):
    """Token stream -> final hidden states (B, S, D)."""
    x = embed_tokens(cfg, params, tokens, mesh)
    if extra_embeds is not None:  # vlm patch embeddings replace a prefix
        pfx = extra_embeds.astype(x.dtype)
        x = jnp.concatenate([pfx, x[:, pfx.shape[1]:]], axis=1)
    positions = positions if positions is not None else (
        _positions_default(cfg, tokens))
    enc_out = None
    if cfg.enc_layers:
        assert enc_frames is not None
        enc_out = encode(cfg, params, enc_frames, mesh)
    x, _ = _run_blocks(cfg, params["blocks"], x, positions, mesh=mesh,
                       enc_out=enc_out, remat=remat)
    return L.norm_apply(cfg, params["final_norm"], x,
                        params.get("final_norm_b"))


def logits_from_hidden(cfg, params, h):
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(mask[None, None, :], logits, -1e30)
    return logits


def forward(cfg, params, tokens, **kw):
    return logits_from_hidden(
        cfg, params, forward_hidden(cfg, params, tokens, **kw))


def lm_loss(cfg, params, batch, mesh=None, loss_chunk=512):
    """Mean next-token CE; the vocab projection + CE run in seq chunks so
    fp32 logits never materialize at (B, S, V)."""
    tokens = batch["tokens"]
    h = forward_hidden(cfg, params, tokens,
                       positions=batch.get("positions"),
                       extra_embeds=batch.get("extra_embeds"),
                       enc_frames=batch.get("enc_frames"), mesh=mesh)
    targets = batch.get("labels", tokens)
    mask = batch.get("mask")
    b, s, _ = h.shape
    h_in = h[:, :-1]
    t_in = targets[:, 1:]
    m_in = (mask[:, 1:] if mask is not None
            else jnp.ones_like(t_in, jnp.float32))
    c = min(loss_chunk, s - 1)
    n_chunks = (s - 1) // c
    trim = n_chunks * c
    hs = h_in[:, :trim].reshape(b, n_chunks, c, -1).transpose(1, 0, 2, 3)
    ts = t_in[:, :trim].reshape(b, n_chunks, c).transpose(1, 0, 2)
    ms = m_in[:, :trim].reshape(b, n_chunks, c).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(carry, inp):
        # checkpointed: backward recomputes the chunk logits instead of
        # keeping (B, chunk, V) fp32 residuals per chunk alive.
        hc, tc, mc = inp
        logits = logits_from_hidden(cfg, params, hc)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (carry[0] + nll.sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(chunk, (jnp.float32(0), jnp.float32(0)),
                                 (hs, ts, ms))
    # remainder tokens (s-1 not divisible by chunk) -- small, direct
    if trim < s - 1:
        logits = logits_from_hidden(cfg, params, h_in[:, trim:])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, t_in[:, trim:][..., None], axis=-1)[..., 0]
        tot = tot + ((lse - gold) * m_in[:, trim:]).sum()
        cnt = cnt + m_in[:, trim:].sum()
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0):
    """Shape/dtype/sharding metadata for the decode cache (one pattern pos)."""
    cd = jnp.dtype(cfg.compute_dtype)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    out = {}
    for i, spec in enumerate(cfg.pattern):
        e = {}
        if spec.mixer == "attn":
            e["k"] = L.PD((batch, max_len, kv, hd), ("dp", "sp", None, None))
            e["v"] = L.PD((batch, max_len, kv, hd), ("dp", "sp", None, None))
        elif spec.mixer == "mla":
            e["ckv"] = L.PD((batch, max_len, cfg.mla.kv_lora),
                            ("dp", "sp", None))
            e["kr"] = L.PD((batch, max_len, cfg.mla.qk_rope_dim),
                           ("dp", "sp", None))
        elif spec.mixer == "mamba":
            e["conv"] = L.PD((batch, cfg.ssm.d_conv - 1, cfg.d_inner),
                             ("dp", None, "tp"))
            e["h"] = L.PD((batch, cfg.d_inner, cfg.ssm.d_state),
                          ("dp", "tp", None))
        if spec.cross_attn:
            e["xk"] = L.PD((batch, enc_len, cfg.n_heads, hd),
                           ("dp", None, "tp", None))
            e["xv"] = L.PD((batch, enc_len, cfg.n_heads, hd),
                           ("dp", None, "tp", None))
        out[f"L{i}"] = e
    stacked = _stack(out, cfg.n_blocks)
    del cd
    return stacked


def abstract_cache(cfg, batch, max_len, enc_len=0):
    cd = jnp.dtype(cfg.compute_dtype)
    defs = cache_defs(cfg, batch, max_len, enc_len)
    flat = _flatten_with_path(defs)
    leaves = [jax.ShapeDtypeStruct(
        pd.shape, jnp.float32 if path.endswith("/h") else cd)
        for path, pd in flat]  # ssm state carries fp32
    treedef = jax.tree_util.tree_structure(
        defs, is_leaf=lambda x: isinstance(x, L.PD))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def cache_pspecs(cfg, batch, max_len, axis_names, enc_len=0):
    return jax.tree.map(lambda pd: to_pspec(pd.axes, axis_names),
                        cache_defs(cfg, batch, max_len, enc_len),
                        is_leaf=lambda x: isinstance(x, L.PD))


def init_cache(cfg, batch, max_len, enc_len=0):
    ab = abstract_cache(cfg, batch, max_len, enc_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ab)


def decode_step(cfg, params, cache, kv_len, tokens, *, positions=None,
                mesh=None):
    """One token for every sequence.  tokens: (B, 1).  Returns (logits, cache)."""
    x = embed_tokens(cfg, params, tokens, mesh)
    if positions is None:
        b = tokens.shape[0]
        positions = jnp.broadcast_to(
            kv_len.astype(jnp.int32)[None, None], (b, 1))
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    x, new_cache = _run_blocks(cfg, params["blocks"], x, positions, mesh=mesh,
                               mode="decode", cache_blocks=cache,
                               kv_len=kv_len)
    h = L.norm_apply(cfg, params["final_norm"], x, params.get("final_norm_b"))
    return logits_from_hidden(cfg, params, h), new_cache


def prefill(cfg, params, tokens, max_len, *, positions=None, enc_frames=None,
            extra_embeds=None, mesh=None):
    """Process the prompt, build the cache.  Returns (last-pos logits, cache)."""
    x = embed_tokens(cfg, params, tokens, mesh)
    if extra_embeds is not None:
        pfx = extra_embeds.astype(x.dtype)
        x = jnp.concatenate([pfx, x[:, pfx.shape[1]:]], axis=1)
    positions = positions if positions is not None else (
        _positions_default(cfg, tokens))
    enc_out = None
    if cfg.enc_layers:
        enc_out = encode(cfg, params, enc_frames, mesh)
    x, cache = _run_blocks(cfg, params["blocks"], x, positions, mesh=mesh,
                           mode="prefill", kv_len=max_len, enc_out=enc_out)
    h = L.norm_apply(cfg, params["final_norm"], x[:, -1:],
                     params.get("final_norm_b"))
    return logits_from_hidden(cfg, params, h), cache
