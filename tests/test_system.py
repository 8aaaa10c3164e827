"""End-to-end behaviour: training reduces loss, the serving engine serves,
and the build layer lowers + compiles on the dev mesh (the same code path
the 512-chip dry-run exercises)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as config_base
from repro.data.tokens import MarkovTokens
from repro.launch.mesh import make_dev_mesh
from repro.models import api
from repro.optim import optimizers as opt_lib
from repro.serve.engine import Request, ServeEngine
from repro.substrate.precision import get_policy
from repro.train import steps as steps_lib

POLICY = get_policy("f32")


def _cost(compiled) -> dict:
    """cost_analysis() returns a per-device list on newer jax versions."""
    ca = compiled.cost_analysis()
    return ca[0] if isinstance(ca, list) else ca


def test_lm_training_reduces_loss():
    """40 steps on the low-entropy Markov stream: loss must drop clearly."""
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    opt = opt_lib.adamw(3e-3)
    ostate = opt.init(params)
    step = jax.jit(steps_lib.make_train_step(model, cfg, opt, POLICY),
                   donate_argnums=(0, 1))
    data = MarkovTokens(cfg.vocab, seed=0)
    losses = []
    for i in range(40):
        batch = {"tokens": jnp.asarray(data.sample(8, 128))}
        params, ostate, m = step(params, ostate, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, \
        losses[:3] + losses[-3:]


def test_ssm_training_reduces_loss():
    """The recurrent family trains too (different gradient path: scans)."""
    cfg = config_base.reduced_config("xlstm-125m")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    opt = opt_lib.adamw(3e-3)
    ostate = opt.init(params)
    step = jax.jit(steps_lib.make_train_step(model, cfg, opt, POLICY),
                   donate_argnums=(0, 1))
    data = MarkovTokens(cfg.vocab, seed=1)
    losses = []
    for i in range(30):
        batch = {"tokens": jnp.asarray(data.sample(8, 128))}
        params, ostate, m = step(params, ostate, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_serve_engine_end_to_end():
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(4):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, 6,
                                               dtype=np.int32),
                           max_new_tokens=5))
    done = eng.run()
    assert len(done) == 4
    for r in done:
        assert len(r.tokens) == 5
        assert all(0 <= t < cfg.vocab for t in r.tokens)


def test_build_lowers_and_compiles_on_dev_mesh():
    """The dry-run build path compiles on the real (1-CPU) mesh for a
    reduced arch — catching spec/tree mismatches without the 512-dev run."""
    import repro.configs.base as cb
    from repro.launch import build as build_lib

    mesh = make_dev_mesh()
    arch = "olmoe-1b-7b"
    orig = cb.get_config
    try:
        cb.get_config = lambda a: (config_base.reduced_config(a)
                                   if a == arch else orig(a))
        with mesh:
            built = build_lib.build_train(arch, "train_4k", mesh,
                                          rules_name="dp")
            b = {"tokens": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
            lowered = built.fn.lower(built.args[0], built.args[1], b)
            compiled = lowered.compile()
            assert _cost(compiled).get("flops", 0) > 0
    finally:
        cb.get_config = orig


def test_gan_build_lowers_on_dev_mesh():
    from repro.launch import build as build_lib
    mesh = make_dev_mesh()
    with mesh:
        built = build_lib.build_gan_train(mesh, reduced=True,
                                          policy_name="f32")
        compiled = built.lower().compile()
        assert _cost(compiled).get("flops", 0) > 0


def test_train_launcher_builtin_gan_runs_in_process(monkeypatch, capsys):
    """`python -m repro.launch.train` with the default builtin loop: the
    launcher's own mesh must accept the step's sharding constraints (a
    mesh with Explicit axes made this raise before any step ran)."""
    from repro.launch import compile_cache, train
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    train.main(["--arch", "calo3dgan", "--reduced", "--steps", "2",
                "--loop", "builtin"])
    out = capsys.readouterr().out
    assert "[step      0" in out and "d_loss_real=" in out
    assert "physics validation:" in out


def test_ragged_engine_matches_single_request():
    """Per-slot vector positions: a request served alongside OTHER ragged
    requests must produce the same tokens as served alone."""
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 9, 7)]

    # alone
    solo = []
    for p in prompts:
        eng = ServeEngine(cfg, params, slots=1, max_len=64)
        eng.submit(Request(rid=0, prompt=p, max_new_tokens=4))
        solo.append(eng.run()[0].tokens)

    # together, ragged
    eng = ServeEngine(cfg, params, slots=3, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    together = {r.rid: r.tokens for r in eng.run()}
    for i in range(3):
        assert together[i] == solo[i], (i, together[i], solo[i])


def test_engine_eos_stops_early():
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, max_len=64)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 5, dtype=np.int32)
    # find what the model emits first, then use it as the eos token
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    first = eng.run()[0].tokens[0]
    eng2 = ServeEngine(cfg, params, slots=1, max_len=64)
    eng2.submit(Request(rid=1, prompt=prompt, max_new_tokens=8,
                        eos_id=int(first)))
    done = eng2.run()[0]
    assert done.tokens[-1] == first
    assert len(done.tokens) < 8


def test_engine_serves_recurrent_arch():
    """The engine is family-agnostic: xlstm's O(1) state cache serves the
    same way as a KV cache."""
    cfg = config_base.reduced_config("xlstm-125m")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(3):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, 4 + rid,
                                               dtype=np.int32),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.tokens) == 4 for r in done)


def test_ragged_engine_recurrent_state_isolation():
    """Recurrent-state version of the ragged test: serving alongside other
    requests must not perturb a request's state (regression for the
    snapshot/merge fix in ServeEngine._prefill_slot)."""
    cfg = config_base.reduced_config("xlstm-125m")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (4, 8)]
    solo = []
    for p in prompts:
        eng = ServeEngine(cfg, params, slots=1, max_len=64)
        eng.submit(Request(rid=0, prompt=p, max_new_tokens=4))
        solo.append(eng.run()[0].tokens)
    eng = ServeEngine(cfg, params, slots=2, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    together = {r.rid: r.tokens for r in eng.run()}
    for i in range(2):
        assert together[i] == solo[i], (i, together[i], solo[i])


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "zamba2-1.2b"])
def test_chunked_prefill_matches_sequential(arch):
    """The chunked batched prefill path must emit BIT-IDENTICAL tokens to
    the legacy sequential prefill, including mid-run slot refills with
    other slots actively decoding (5 requests through 3 slots)."""
    cfg = config_base.reduced_config(arch)
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n in (5, 12, 3, 9, 7)]

    results = {}
    for mode in ("sequential", "chunked"):
        eng = ServeEngine(cfg, params, slots=3, max_len=64,
                          prefill=mode, prefill_chunk=4)
        assert eng.prefill_mode == mode
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
        results[mode] = {r.rid: r.tokens for r in eng.run()}
    assert results["chunked"] == results["sequential"]


def test_chunked_prefill_freezes_other_slots():
    """A chunked prefill of a newly-filled slot must not advance the
    decode position or next-token state of slots that are mid-decode."""
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64,
                      prefill="chunked", prefill_chunk=4)
    rng = np.random.default_rng(2)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6,
                                                  dtype=np.int32),
                       max_new_tokens=10))
    eng._fill_slots()
    eng._step()
    pos0, tok0 = int(eng.pos[0]), int(eng.cur_tok[0, 0])

    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 11,
                                                  dtype=np.int32),
                       max_new_tokens=10))
    eng._fill_slots()            # chunked prefill of slot 1 only
    assert int(eng.pos[0]) == pos0
    assert int(eng.cur_tok[0, 0]) == tok0
    assert int(eng.pos[1]) == 11
    done = eng.run()
    assert sorted(len(r.tokens) for r in done) == [10, 10]


def test_chunked_prefill_mode_validation():
    """auto falls back to sequential for archs without a chunked prefill
    path; asking for chunked explicitly there is an error."""
    cfg = config_base.reduced_config("xlstm-125m")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, max_len=64, prefill="auto")
    assert eng.prefill_mode == "sequential"
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, slots=1, max_len=64, prefill="chunked")
    kcfg = config_base.reduced_config("qwen2-1.5b")
    kmodel = api.get_model(kcfg)
    kparams = kmodel.init(jax.random.key(0), kcfg)
    keng = ServeEngine(kcfg, kparams, slots=1, max_len=64)
    assert keng.prefill_mode == "chunked"     # auto picks it up


def test_engine_deadline_expires_in_flight_request():
    """A request whose SLA deadline passes MID-DECODE is rejected with a
    structured deadline rejection and frees its slot (regression: the
    sweep used to cover only queued requests)."""
    class _Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = _Clock()
    cfg = config_base.reduced_config("qwen2-1.5b")
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    eng = ServeEngine(cfg, params, slots=1, max_len=64, clock=clk)
    rng = np.random.default_rng(0)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 5,
                                                  dtype=np.int32),
                       max_new_tokens=20, deadline_s=5.0))
    eng._fill_slots()
    eng._step()
    eng._step()
    clk.t = 10.0                 # SLA blown with the request in a slot
    eng._sweep_slot_deadlines()
    assert eng.slot_req[0] is None
    (req,) = eng.rejected
    assert req.status == "rejected"
    assert req.error["reason"] == "deadline"
    assert "mid-decode" in req.error["detail"]
    assert 0 < len(req.tokens) < 20
    assert eng.run() == []       # engine is drained and idle again
