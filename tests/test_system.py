"""End-to-end behaviour: the paper's full pipeline on real (synthetic) data.

query → Σ from data → Δ (learned or analytic) → Alg. 1 synthesis →
lowered vectorized execution → correct answers; plus the serve loop and a
micro training run — the whole system touched in one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost import AnalyticCostModel
from repro.core.synthesis import synthesize
from repro.data import tpch
from repro.data.table import collect_stats
from repro.exec.queries import QUERIES


@pytest.fixture(scope="module")
def db():
    return tpch.generate(scale=0.002, seed=5).tables()


@pytest.fixture(scope="module")
def delta():
    # use the installed learned model when present, analytic prior otherwise
    from repro.costmodel import load_model

    return load_model() or AnalyticCostModel()


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_synthesis_to_execution(qname, db, delta):
    """Alg. 1 choices plugged into the lowered plan produce correct answers."""
    q = QUERIES[qname]
    sigma = collect_stats(db)
    res = synthesize(q.llql(), sigma, delta)
    assert res.choices, "synthesis produced no dictionary choices"
    got = q.run(db, res.choices)
    ref = q.reference(db)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=3e-3, atol=3e-2)


def test_fine_tuned_beats_or_ties_single_dicts(db, delta):
    """The paper's core claim in miniature: the cost-model choice is never
    worse (in estimated cost) than any single-implementation plan."""
    from repro.core.cost import DictChoice, infer_cost

    q = QUERIES["q18"]
    sigma = collect_stats(db)
    prog = q.llql()
    tuned = synthesize(prog, sigma, delta)
    costs = {}
    for ds in ("ht_linear", "ht_twochoice", "st_sorted", "st_blocked"):
        gamma = {s: DictChoice(ds) for s in tuned.choices}
        costs[ds] = infer_cost(prog, sigma, delta, gamma).total
    assert tuned.cost.total <= min(costs.values()) + 1e-12


def test_serve_end_to_end():
    from repro.models.registry import get_model_by_name
    from repro.serve.serve_loop import Request, Server

    m = get_model_by_name("llama3.2-3b", reduced=True)
    params = m.init(jax.random.PRNGKey(0))
    srv = Server(m, params, batch_slots=2, cache_len=48, eos=-1)
    for i in range(4):
        srv.submit(Request(rid=i, prompt=[i + 1, 2], max_new=5))
    done = srv.run_until_done()
    assert len(done) == 4
    assert all(len(r.out) == 5 for r in done)
    assert all(0 <= t < m.cfg.vocab for r in done for t in r.out)


def test_train_e2e_loss_decreases(tmp_path):
    from repro.data.lm_data import StreamConfig
    from repro.train.optimizer import OptConfig
    from repro.train.train_loop import TrainConfig, Trainer
    from repro.models.registry import get_model_by_name

    m = get_model_by_name("granite-20b", reduced=True)
    scfg = StreamConfig(vocab=m.cfg.vocab, global_batch=4, seq_len=24, seed=0)
    tc = TrainConfig(
        steps=8, ckpt_every=100, ckpt_dir=str(tmp_path), ckpt_async=False,
        log_every=1000, opt=OptConfig(lr=2e-3, warmup_steps=2, total_steps=8),
    )
    t = Trainer(m, tc, scfg)
    t.init()
    log = t.run()
    assert log[-1]["loss"] < log[0]["loss"]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """``repro.connect`` turns on jax's persistent compile cache: in
    ``$JAX_COMPILATION_CACHE_DIR`` when set (and no other directory), else
    in one fixed directory at the checkout root that git ignores."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(root, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax, repro\n"
        "from repro.data import tpch\n"
        "from repro.session import COMPILE_CACHE_DIR\n"
        "repro.connect(tpch.generate(scale=0.001, seed=0).tables())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(COMPILE_CACHE_DIR)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    used, fixed = out.stdout.split()[-2:]
    if from_env:
        assert used == str(tmp_path)
    else:
        assert used == fixed
        assert os.path.dirname(fixed) == os.path.realpath(root)
        with open(os.path.join(root, ".gitignore")) as f:
            ignored = {line.strip().strip("/") for line in f}
        assert os.path.basename(fixed) in ignored, f"{fixed} is not gitignored"
