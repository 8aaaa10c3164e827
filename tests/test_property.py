"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel import collectives, sharding
from repro.substrate import attention as attn_lib
from repro.substrate import layers

SETTINGS = dict(max_examples=8, deadline=None)


# ---------------------------------------------------------------------------
# sharding spec resolution
# ---------------------------------------------------------------------------


@given(
    dims=st.lists(st.integers(1, 512), min_size=1, max_size=4),
    axis_names=st.permutations(("embed", "heads", "mlp", "vocab")),
)
@settings(**SETTINGS)
def test_resolve_spec_invariants(dims, axis_names):
    """For ANY shape/logical-axis combination: every mesh axis appears at
    most once, and every sharded dim is divisible by its axis size."""
    mesh = make_mesh((1, 1), ("data", "model"))
    logical = tuple(axis_names[:len(dims)])
    spec = sharding.resolve_spec(logical, tuple(dims), mesh,
                                 sharding.FSDP_TP_RULES)
    used = [a for entry in spec for a in
            ((entry,) if isinstance(entry, str) else (entry or ()))]
    assert len(used) == len(set(used))
    for dim, entry in zip(dims, spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        size = int(np.prod([mesh.shape[a] for a in axes]))
        assert dim % size == 0


@given(st.integers(1, 64), st.integers(1, 8))
@settings(**SETTINGS)
def test_moe_group_pick_divides(T_mult, target_log):
    from repro.substrate.moe import _pick_groups
    T = T_mult * 8
    G = _pick_groups(T, 2 ** target_log)
    assert T % G == 0
    assert 1 <= G <= T


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


@given(
    s=st.integers(2, 8),
    d_half=st.sampled_from((4, 8, 16)),
    scale=st.floats(0.1, 10.0),
)
@settings(**SETTINGS)
def test_rope_is_isometry(s, d_half, scale):
    """RoPE rotation preserves vector norms for any position/scale."""
    d = 2 * d_half
    pos = jnp.broadcast_to(jnp.arange(s)[None], (1, s))
    cos, sin = attn_lib.rope_cos_sin(pos, d, 10_000.0)
    x = scale * jax.random.normal(jax.random.key(s), (1, s, 2, d))
    r = attn_lib.apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(r, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-4)


@given(
    b=st.integers(1, 3), s=st.integers(1, 32),
    scale=st.floats(0.5, 100.0),     # >= 0.5: below that the eps term in
                                     # rsqrt(var + 1e-5) legitimately bites
)
@settings(**SETTINGS)
def test_rmsnorm_output_rms_is_one(b, s, scale):
    p = layers.init_norm(64, "rmsnorm")
    x = scale * jax.random.normal(jax.random.key(b * 100 + s), (b, s, 64))
    y = layers.apply_norm(p, x, "rmsnorm")
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(y), axis=-1)))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_softmax_attention_rows_sum_to_one(seed):
    """Attention output of constant-value V equals that constant: the
    softmax weights sum to 1 for every query — incl. masked rows."""
    k1, k2 = jax.random.split(jax.random.key(seed))
    q = jax.random.normal(k1, (1, 16, 2, 8))
    k = jax.random.normal(k2, (1, 16, 2, 8))
    v = jnp.full((1, 16, 2, 8), 3.5)
    out = attn_lib.dot_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), 3.5, atol=1e-5)
    out_b = attn_lib.blockwise_attention(q, k, v, causal=True,
                                         q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out_b), 3.5, atol=1e-5)


# ---------------------------------------------------------------------------
# optimizer state / checkpoint
# ---------------------------------------------------------------------------


@given(
    shapes=st.lists(st.tuples(st.integers(1, 5), st.integers(1, 5)),
                    min_size=1, max_size=4),
    seed=st.integers(0, 1000),
)
@settings(**SETTINGS)
def test_checkpoint_roundtrip_any_tree(tmp_path_factory, shapes, seed):
    from repro.train import checkpoint as ckpt_lib
    rng = np.random.default_rng(seed)
    tree = {f"p{i}": {"w": jnp.asarray(rng.normal(size=s), jnp.float32)}
            for i, s in enumerate(shapes)}
    path = str(tmp_path_factory.mktemp("ck"))
    ckpt_lib.save(path, tree, step=seed)
    back = ckpt_lib.restore(path, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 200), st.integers(1, 50))
@settings(**SETTINGS)
def test_epoch_iterator_covers_everything(n_per_shard, batch):
    """iter_epoch yields every index at most once and >= floor coverage."""
    import tempfile
    from repro.data.pipeline import ShardStore
    with tempfile.TemporaryDirectory() as d:
        store = ShardStore(d)
        store.write("s0", {"id": np.arange(n_per_shard, dtype=np.int64)})
        seen = []
        for b in store.iter_epoch(batch=batch, shuffle_seed=1):
            seen.extend(b["id"].tolist())
        assert len(seen) == len(set(seen))
        assert len(seen) == (n_per_shard // batch) * batch


# ---------------------------------------------------------------------------
# gradient bucket planning (elastic PR: the packing the 2-level reduction
# and the interconnect model both assume)
# ---------------------------------------------------------------------------


_LEAF = st.tuples(st.integers(1, 3000),
                  st.sampled_from(("float32", "bfloat16", "int32")))


@given(leaves=st.lists(_LEAF, min_size=0, max_size=12),
       bucket_kb=st.sampled_from((1, 4, 16)))
@settings(max_examples=25, deadline=None)
def test_plan_buckets_greedy_packing_invariants(leaves, bucket_kb):
    """For ANY leaf sizes/dtypes: the plan partitions the leaf indices
    EXACTLY in flatten order, every bucket is dtype-uniform (buckets are
    concatenated), and no bucket exceeds the cap unless it is a single
    oversize leaf."""
    arrs = [np.zeros(n, jnp.dtype(d)) for n, d in leaves]
    cap = bucket_kb * 1024
    plan = collectives.plan_buckets(arrs, cap)
    assert [i for b in plan for i in b] == list(range(len(arrs)))
    for b in plan:
        assert len({arrs[i].dtype for i in b}) <= 1
        total = sum(arrs[i].size * arrs[i].dtype.itemsize for i in b)
        assert total <= cap or len(b) == 1


def test_plan_buckets_rejects_nonpositive_cap():
    with pytest.raises(ValueError, match="bucket_bytes"):
        collectives.plan_buckets([np.zeros(4, np.float32)], 0)


@given(leaves=st.lists(_LEAF, min_size=0, max_size=12),
       bucket_kb=st.sampled_from((1, 4, 16)))
@settings(max_examples=25, deadline=None)
def test_reverse_bucket_schedule_is_exact_permutation(leaves, bucket_kb):
    """The overlap reducer's issue order: reverse_bucket_schedule must be
    EXACTLY plan_buckets reversed — same buckets, same intra-bucket leaf
    order, no leaf dropped or duplicated.  (A dropped leaf would silently
    skip its gradient reduction; a duplicate would double-reduce.)"""
    arrs = [np.zeros(n, jnp.dtype(d)) for n, d in leaves]
    cap = bucket_kb * 1024
    plan = collectives.plan_buckets(arrs, cap)
    sched = collectives.reverse_bucket_schedule(arrs, cap)
    assert sched == list(reversed(plan))
    flat = sorted(i for b in sched for i in b)
    assert flat == list(range(len(arrs)))


# ---------------------------------------------------------------------------
# checkpoint roundtrip over random pytrees / dtypes / shardings
# ---------------------------------------------------------------------------


_CKPT_LEAF = st.tuples(
    st.lists(st.integers(1, 5), min_size=0, max_size=3),   # shape (incl. 0-d)
    st.sampled_from(("float32", "float16", "int32")))


@given(leaves=st.lists(_CKPT_LEAF, min_size=1, max_size=5),
       seed=st.integers(0, 1000), nest=st.booleans())
@settings(**SETTINGS)
def test_checkpoint_roundtrip_random_pytrees(tmp_path_factory, leaves,
                                             seed, nest):
    """save -> restore is the identity for ANY pytree of mesh-placed
    arrays (mixed shapes/dtypes, flat or nested), preserving dtype; and
    dropping ANY leaf from the template raises naming its key path (the
    strict-restore contract)."""
    from repro.train import checkpoint as ckpt_lib
    mesh = make_mesh((1,), ("data",))
    rep = jax.sharding.NamedSharding(mesh, P())
    rng = np.random.default_rng(seed)
    tree = {}
    for i, (shape, dt) in enumerate(leaves):
        leaf = jnp.asarray(rng.normal(size=shape) * 10, jnp.dtype(dt))
        tree[f"p{i}"] = {"w": jax.device_put(leaf, rep)} if nest \
            else jax.device_put(leaf, rep)
    path = str(tmp_path_factory.mktemp("ck"))
    ckpt_lib.save(path, tree, step=seed)
    back = ckpt_lib.restore(path, jax.tree.map(np.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    victim = f"p{rng.integers(len(leaves))}"
    partial = {k: v for k, v in tree.items() if k != victim}
    if partial:
        with pytest.raises(ValueError, match=victim):
            ckpt_lib.restore(path, jax.tree.map(np.zeros_like, partial))


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------


@given(
    trip=st.integers(1, 100),
    dim0=st.integers(1, 64),
    dim1=st.sampled_from((1, 8, 128)),
    dtype=st.sampled_from(("f32", "bf16", "s32")),
)
@settings(**SETTINGS)
def test_collective_scaling_parametric(trip, dim0, dim1, dtype):
    nbytes = {"f32": 4, "bf16": 2, "s32": 4}[dtype]
    hlo = f"""\
HloModule m

%body.7 (p: (s32[], {dtype}[{dim0},{dim1}])) -> (s32[], {dtype}[{dim0},{dim1}]) {{
  %ar = {dtype}[{dim0},{dim1}] all-reduce(%x), to_apply=%add
  ROOT %t = (s32[], {dtype}[{dim0},{dim1}]) tuple(%i, %ar)
}}

%cond.7 (p: (s32[], {dtype}[{dim0},{dim1}])) -> pred[] {{
  %lim = s32[] constant({trip})
  ROOT %cmp = pred[] compare(%iter, %lim), direction=LT
}}

ENTRY %main (a: {dtype}[{dim0},{dim1}]) -> {dtype}[{dim0},{dim1}] {{
  %w = (s32[], {dtype}[{dim0},{dim1}]) while(%init), condition=%cond.7, body=%body.7
  ROOT %out = {dtype}[{dim0},{dim1}] get-tuple-element(%w), index=1
}}
"""
    stats = collectives.collective_stats(hlo)
    assert stats["all-reduce"]["bytes"] == trip * dim0 * dim1 * nbytes
    assert stats["all-reduce"]["count"] == trip


# ---------------------------------------------------------------------------
# generic kernel-autotune registry (kernels/autotune)
# ---------------------------------------------------------------------------


def _random_signature(draw):
    """A random signature from a random registered family, with the
    matching random schedule."""
    from repro.kernels import autotune as autotune_lib
    from repro.kernels.conv3d import tiles as conv_tiles
    from repro.kernels.flash_attention import tune as attn_tune
    from repro.kernels.ssm_scan import tune as ssm_tune

    family = draw(st.sampled_from(("conv3d", "attn", "ssm")))
    dtype = draw(st.sampled_from((None, jnp.float32, jnp.bfloat16)))
    dim = st.integers(1, 512)
    if family == "conv3d":
        sig = conv_tiles.signature(
            draw(st.sampled_from(("conv", "conv_t", "dw", "dw_t"))),
            tuple(draw(st.lists(dim, min_size=3, max_size=3))),
            draw(dim), draw(dim), 3, draw(st.sampled_from((1, 2))), dtype)
        sched = conv_tiles.ConvTiles(
            bn=draw(st.sampled_from((8, 64, 128))),
            fuse_taps=draw(st.booleans()))
    elif family == "attn":
        sig = attn_tune.signature(draw(dim), draw(dim), draw(dim),
                                  draw(dim), draw(dim),
                                  draw(st.booleans()), draw(dim), dtype)
        sched = attn_tune.AttnBlocks(
            block_q=draw(st.sampled_from((32, 128, 512))),
            block_kv=draw(st.sampled_from((32, 128, 512))))
    else:
        sig = ssm_tune.signature(draw(dim), draw(dim), draw(dim),
                                 draw(dim), dtype)
        sched = ssm_tune.ScanChunks(chunk=draw(st.sampled_from((16, 64,
                                                                256))))
    return sig, sched


@given(data=st.data())
@settings(**SETTINGS)
def test_autotune_cache_roundtrip_any_family(data, tmp_path_factory):
    """save_cache -> load_cache is the identity for ANY signature of ANY
    registered family — the cross-process contract every kernel's
    schedule lookup relies on."""
    from repro.kernels import autotune as autotune_lib

    cache = str(tmp_path_factory.mktemp("autotune"))
    entries = {}
    for _ in range(data.draw(st.integers(1, 4))):
        sig, sched = _random_signature(data.draw)
        entries[sig] = sched
    try:
        autotune_lib.clear_registry()     # warm-loaded entries would leak
        for sig, sched in entries.items():
            autotune_lib.register_schedule(sig, sched)
        autotune_lib.save_cache(cache_dir=cache)
        autotune_lib.clear_registry()
        n = autotune_lib.load_cache(cache_dir=cache)
        assert n == len(entries)
        for sig, sched in entries.items():
            assert autotune_lib.get_schedule(sig) == sched
    finally:
        autotune_lib.clear_registry()


@given(data=st.data(), garbage=st.text(max_size=64))
@settings(**SETTINGS)
def test_autotune_corrupt_cache_falls_back_to_default(data, garbage,
                                                      tmp_path_factory):
    """ANY corrupt cache content must never break a schedule lookup —
    get_schedule's lazy warm-load swallows it and falls back to the
    family heuristic default."""
    import os

    from repro.kernels import autotune as autotune_lib

    cache = tmp_path_factory.mktemp("autotune")
    kind = autotune_lib._device_kind()
    (cache / f"{kind}.json").write_text(garbage)
    sig, _ = _random_signature(data.draw)
    old_env = os.environ.get("REPRO_AUTOTUNE_DIR")
    os.environ["REPRO_AUTOTUNE_DIR"] = str(cache)
    try:
        autotune_lib.clear_registry()
        assert autotune_lib.load_cache(cache_dir=str(cache)) == 0
        # the warm-load path inside get_schedule reads the same corrupt
        # file (via REPRO_AUTOTUNE_DIR) and must still yield the default
        assert autotune_lib.get_schedule(sig) == \
            autotune_lib.default_schedule(sig)
    finally:
        autotune_lib.clear_registry()
        if old_env is None:
            os.environ.pop("REPRO_AUTOTUNE_DIR", None)
        else:
            os.environ["REPRO_AUTOTUNE_DIR"] = old_env


@given(data=st.data())
@settings(**SETTINGS)
def test_autotune_candidates_nonempty_and_schedule_valid(data):
    """For ANY shape, every family's candidate space is non-empty, holds
    only instances of the family's schedule class, and contains the
    heuristic default's type."""
    import dataclasses as dc

    from repro.kernels import autotune as autotune_lib

    sig, _ = _random_signature(data.draw)
    spec = autotune_lib.spec_for(sig)
    cands = autotune_lib.candidate_schedules(sig)
    assert cands
    for c in cands:
        assert isinstance(c, spec.schedule_cls)
        for f in dc.fields(c):
            v = getattr(c, f.name)
            if isinstance(v, int) and not isinstance(v, bool):
                assert v > 0, f"non-positive schedule field {f.name}={v}"
    assert isinstance(autotune_lib.default_schedule(sig),
                      spec.schedule_cls)


@given(data=st.data())
@settings(**SETTINGS)
def test_autotune_manual_registration_beats_disk(data, tmp_path_factory):
    """An in-memory register_schedule always wins over a different
    schedule persisted on disk for the same signature."""
    from repro.kernels import autotune as autotune_lib

    cache = str(tmp_path_factory.mktemp("autotune"))
    sig, disk_sched = _random_signature(data.draw)
    manual = autotune_lib.default_schedule(sig)
    if manual == disk_sched:        # make them observably different
        import dataclasses as dc
        f = dc.fields(disk_sched)[0].name
        v = getattr(disk_sched, f)
        disk_sched = dc.replace(
            disk_sched, **{f: (v + 1 if isinstance(v, int)
                               and not isinstance(v, bool) else not v)})
    try:
        autotune_lib.register_schedule(sig, disk_sched)
        autotune_lib.save_cache(cache_dir=cache)
        autotune_lib.clear_registry()
        autotune_lib.register_schedule(sig, manual)
        autotune_lib.load_cache(cache_dir=cache)
        assert autotune_lib.get_schedule(sig) == manual
    finally:
        autotune_lib.clear_registry()


# ---------------------------------------------------------------------------
# flash-decode split-KV combine: invariant to the split partition
# ---------------------------------------------------------------------------


@given(data=st.data())
@settings(**SETTINGS)
def test_decode_combine_invariant_to_split_partition(data):
    """For ANY contiguous partition of the KV axis (any split count, any
    cut points, empty splits included) and ANY order of the splits, the
    online-softmax combine equals the direct un-split softmax."""
    from repro.kernels.flash_attention import decode as decode_lib

    G = data.draw(st.integers(1, 4), label="groups")
    T = data.draw(st.integers(1, 64), label="kv_len")
    D = data.draw(st.integers(1, 8), label="d_head")
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31), "seed"))
    s = jnp.asarray(rng.normal(0, 3, (G, T)), jnp.float32)
    vv = jnp.asarray(rng.normal(0, 1, (T, D)), jnp.float32)
    direct = jax.nn.softmax(s, axis=-1) @ vv

    n_cuts = data.draw(st.integers(0, 6), label="n_cuts")
    cuts = sorted(data.draw(st.lists(st.integers(0, T), min_size=n_cuts,
                                     max_size=n_cuts), label="cuts"))
    bounds = list(zip([0] + cuts, cuts + [T]))      # may contain empties
    order = data.draw(st.permutations(range(len(bounds))), label="order")

    accs, ms, ls = [], [], []
    for i in order:
        lo, hi = bounds[i]
        if hi == lo:                                # empty split partial
            accs.append(jnp.zeros((G, D)))
            ms.append(jnp.full((G,), decode_lib.NEG_INF))
            ls.append(jnp.zeros((G,)))
        else:
            blk = s[:, lo:hi]
            m = jnp.max(blk, axis=-1)
            e = jnp.exp(blk - m[:, None])
            accs.append(e @ vv[lo:hi])
            ms.append(m)
            ls.append(jnp.sum(e, axis=-1))
    out = decode_lib.combine_splits(jnp.stack(accs), jnp.stack(ms),
                                    jnp.stack(ls))
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               atol=2e-5)
