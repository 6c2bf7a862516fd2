"""Per-arch smoke tests: every assigned architecture, reduced config,
one real forward/train step on CPU, asserting shapes + finite outputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import gnn as gnn_lib
from repro.models import recsys as rec_lib
from repro.models import transformer as tfm
from repro.train import data as data_lib
from repro.train import optimizer as opt_lib

LM_ARCHS = ["gemma3-4b", "minicpm3-4b", "qwen3-0.6b", "mixtral-8x7b",
            "mixtral-8x22b"]
REC_ARCHS = ["sasrec", "bert4rec", "dien", "xdeepfm"]


def _init_for(arch, cfg, key):
    if arch.kind == "lm":
        return tfm.init_params(key, cfg)
    if arch.kind == "gnn":
        return gnn_lib.init_params(key, cfg)
    return {"sasrec": rec_lib.init_sasrec, "bert4rec": rec_lib.init_bert4rec,
            "dien": rec_lib.init_dien,
            "xdeepfm": rec_lib.init_xdeepfm}[arch.arch_id](key, cfg)


def _batch_for(arch, cfg, shp, seed=0):
    if arch.kind == "lm":
        return data_lib.lm_batch(seed, 0, shp["batch"], shp["seq"],
                                 cfg.vocab)
    if arch.kind == "gnn":
        if shp.get("graph_level"):
            return data_lib.molecule_batch(seed, 0, shp["n_graphs"],
                                           shp["n_nodes"] // shp["n_graphs"],
                                           shp["n_edges"] // shp["n_graphs"],
                                           cfg.d_feat, cfg.n_classes)
        g = data_lib.make_synthetic_graph(shp["n_nodes"], shp["n_edges"],
                                          cfg.d_feat, cfg.n_classes, seed)
        return data_lib.fullgraph_batch(g, seed=seed)
    aid = arch.arch_id
    if aid == "sasrec":
        return data_lib.sasrec_batch(seed, 0, shp["batch"], cfg.seq_len,
                                     cfg.n_items, cfg.n_negatives)
    if aid == "bert4rec":
        return data_lib.bert4rec_batch(seed, 0, shp["batch"], cfg.seq_len,
                                       cfg.n_items, cfg.n_negatives)
    if aid == "dien":
        return data_lib.dien_batch(seed, 0, shp["batch"], cfg.seq_len,
                                   cfg.n_items)
    return data_lib.xdeepfm_batch(seed, 0, shp["batch"], cfg.n_fields,
                                  cfg.field_vocab, cfg.n_hot)


@pytest.mark.parametrize("arch_id", list(configs.ARCHS))
def test_train_step_smoke(arch_id):
    """One REAL train step (init'd params + AdamW) per arch."""
    arch = configs.get_arch(arch_id)
    shape_id = next(s for s, v in arch.smoke_shapes.items()
                    if v.get("step", "train") == "train"
                    or arch.kind == "gnn")
    shp = arch.smoke_shapes[shape_id]
    cfg = arch.make_config("smoke", shape_id)
    params = _init_for(arch, cfg, jax.random.PRNGKey(0))
    batch = jax.tree.map(jnp.asarray, _batch_for(arch, cfg, shp))

    if arch.kind == "lm":
        loss_fn = lambda p, b: tfm.loss_fn(p, cfg, b)       # noqa: E731
    elif arch.kind == "gnn":
        loss_fn = ((lambda p, b: gnn_lib.graph_loss(p, cfg, b))
                   if shp.get("graph_level")
                   else (lambda p, b: gnn_lib.node_loss(p, cfg, b)))
    else:
        lf = {"sasrec": rec_lib.sasrec_loss,
              "bert4rec": rec_lib.bert4rec_loss,
              "dien": rec_lib.dien_loss,
              "xdeepfm": rec_lib.xdeepfm_loss}[arch_id]
        loss_fn = lambda p, b: lf(p, cfg, b)                # noqa: E731

    step = jax.jit(opt_lib.make_train_step(
        loss_fn, opt_lib.AdamWConfig(lr=1e-3, warmup_steps=1,
                                     total_steps=10)))
    new_p, new_s, metrics = step(params, opt_lib.init(params), batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), (arch_id, loss)
    for leaf in jax.tree.leaves(new_p):
        assert np.isfinite(np.asarray(leaf)).all(), arch_id
    # params actually moved
    moved = any(not np.allclose(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(new_p)))
    assert moved, arch_id


@pytest.mark.parametrize("arch_id", LM_ARCHS)
def test_lm_serve_smoke(arch_id):
    """prefill + decode consistency for every LM arch (reduced config).

    MoE capacity is raised so it does not bind: capacity-based MoE is
    inherently batch-dependent (drop patterns differ between the 15- and
    16-token prefills), which is a property, not a bug — the equivalence
    being tested is the attention/cache path.
    """
    import dataclasses
    arch = configs.get_arch(arch_id)
    cfg = arch.make_config("smoke", "decode_32k")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
    pr_full = jax.jit(lambda p: tfm.prefill(p, cfg, toks))(params)
    pr_part = jax.jit(lambda p: tfm.prefill(p, cfg, toks[:, :15]))(params)
    cache = tfm.pad_cache(pr_part.cache, 16, cfg)
    logits, _, _ = jax.jit(
        lambda p, c: tfm.decode_step(p, cfg, c, toks[:, 15:16],
                                     pr_part.cache_len))(params, cache)
    a, b = np.asarray(logits), np.asarray(pr_full.logits)
    rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
    # MLA decode uses the absorbed form (different bf16 contraction order)
    tol = 2e-2 if cfg.attn == "mla" else 1e-3
    assert rel < tol, (arch_id, rel)
    assert np.isfinite(a).all()


@pytest.mark.parametrize("arch_id", REC_ARCHS)
def test_recsys_serve_and_retrieval_smoke(arch_id):
    arch = configs.get_arch(arch_id)
    for shape_id in ("serve_p99", "retrieval_cand"):
        cell = arch.cell(shape_id, scale="smoke")
        cfg = arch.make_config("smoke", shape_id)
        params = _init_for(arch, cfg, jax.random.PRNGKey(3))
        rng = np.random.default_rng(0)
        rest = []
        for a in cell.abstract_args[1:]:
            rest.append(jax.tree.map(
                lambda x: jnp.asarray(
                    rng.integers(0, 50, x.shape).astype(np.int32))
                if x.dtype == jnp.int32
                else jnp.asarray(rng.normal(size=x.shape).astype(np.float32)),
                a))
        out = jax.jit(cell.fn)(params, *rest)
        for leaf in jax.tree.leaves(out):
            arr = np.asarray(leaf)
            if arr.dtype.kind == "f":
                assert np.isfinite(arr).all(), (arch_id, shape_id)


def test_gemma3_local_global_pattern():
    cfg = configs.get_arch("gemma3-4b").make_config("full")
    pat = np.asarray(cfg.layer_is_global())
    assert pat.sum() == 34 // 6               # every 6th layer is global
    assert not pat[:5].any() and pat[5]       # 5 local then 1 global


def test_moe_capacity_drops_tokens():
    """Over-capacity tokens are dropped, not mis-routed."""
    cfg = tfm.MoeConfig(n_experts=2, top_k=1, capacity_factor=0.25,
                        groups=1)
    prm = {
        "router": jnp.asarray(np.eye(8, 2, dtype=np.float32) * 10),
        "w_gate": jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 8, 16)).astype(np.float32)),
        "w_up": jnp.asarray(np.random.default_rng(1).normal(
            size=(2, 8, 16)).astype(np.float32)),
        "w_down": jnp.asarray(np.random.default_rng(2).normal(
            size=(2, 16, 8)).astype(np.float32)),
    }
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(16, 8)).astype(np.float32))
    out = tfm._moe_ffn(prm, x, cfg, jnp.float32)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    # capacity 0.25 * 16 / 2 = 2 slots/expert -> most tokens dropped (zero)
    zeros = (np.abs(np.asarray(out)).sum(-1) == 0).sum()
    assert zeros >= 8


def test_ring_cache_matches_full_cache():
    """SWA ring cache (window-sized) decodes identically to a full-length
    cache once the window wraps — the layout cut is semantics-free."""
    import dataclasses
    cfg_full = tfm.TransformerConfig(
        name="swa", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab=128, window=8, global_every=0,
        chunk_q=8, loss_chunk=8, ring_cache=False)
    cfg_ring = dataclasses.replace(cfg_full, ring_cache=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_full)
    B, steps = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, steps), 0, 128)

    def run(cfg, slots):
        cache = tfm.init_cache(cfg, B, slots)
        cl = jnp.zeros((B,), jnp.int32)
        outs = []
        step = jax.jit(lambda c, t, l: tfm.decode_step(params, cfg, c, t, l))
        for i in range(steps):
            logits, cache, cl = step(cache, toks[:, i:i + 1], cl)
            outs.append(np.asarray(logits))
        return np.stack(outs)

    full = run(cfg_full, steps)
    ring = run(cfg_ring, steps)          # allocates only `window` slots
    assert tfm.cache_slots(cfg_ring, steps) == 8
    np.testing.assert_allclose(ring, full, rtol=2e-3, atol=2e-3)


def test_bucketed_retrieval_recall():
    """The sort-free bucketed top-k (used for sharded serving) must keep
    high recall vs exact top-k, and the iterative top-k must be EXACT."""
    from repro.models import recsys
    rng = np.random.default_rng(0)
    uv = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    cand = jnp.asarray(rng.normal(size=(4096, 16)).astype(np.float32))
    k = 32
    exact_v, exact_i = jax.lax.top_k(uv @ cand.T, k)

    # iterative_topk is exact
    it_v, it_i = recsys.iterative_topk(jnp.asarray(uv @ cand.T), k)
    np.testing.assert_allclose(np.asarray(it_v), np.asarray(exact_v),
                               rtol=1e-6)

    # bucketed pipeline (chunked path): measure recall@k
    with jax.make_mesh((1,), ("data",),
                       axis_types=(jax.sharding.AxisType.Auto,)):
        bk_v, bk_i = recsys.retrieval_topk(uv, cand, k=k, chunk=512,
                                           batch_axes=("data",))
    recall = np.mean([
        len(set(np.asarray(bk_i[b]).tolist()) &
            set(np.asarray(exact_i[b]).tolist())) / k
        for b in range(8)])
    assert recall >= 0.85, recall
    # and every returned score must be a TRUE score of its returned id
    full = np.asarray(uv @ cand.T)
    for b in range(8):
        for v, i in zip(np.asarray(bk_v[b]), np.asarray(bk_i[b])):
            np.testing.assert_allclose(v, full[b, i], rtol=1e-5)
