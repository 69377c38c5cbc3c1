"""repro.geometry: retraction axioms for every registered geometry x
retraction, the fused Pallas retraction vs the eigh oracle, the ManifoldMap
back-compat shim, Product-manifold ops, and the Grassmann robust-PCA
workload."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import geometry as G
from repro.core import manifolds as M
from repro.kernels import ops

SET = dict(deadline=None, max_examples=12)

# (geometry, retraction) pairs under the axiom suite; polar_fused is
# exercised separately (it takes ambient directions and needs the ops
# dispatch), Euclidean is trivially exact for every axiom.
CASES = [(name, kind)
         for name, m in sorted(G.REGISTRY.items())
         for kind in m.retractions if kind != "polar_fused"]


@st.composite
def dims(draw):
    d = draw(st.integers(3, 48))
    r = draw(st.integers(1, min(d, 12)))
    seed = draw(st.integers(0, 2 ** 16))
    return d, r, seed


def _point_and_tangent(m: G.Manifold, d, r, seed, scale=0.2):
    x = m.rand(jax.random.PRNGKey(seed), d, r)
    g = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, r))
    u = m.tangent_project(x, g)
    nrm = jnp.maximum(jnp.linalg.norm(u), 1e-9)
    return x, scale * u / nrm


# ---------------------------------------------------------------------------
# retraction axioms: every geometry x retraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,kind", CASES)
def test_retraction_axioms(name, kind):
    m = G.get(name)

    @given(dims())
    @settings(**SET)
    def run(drs):
        d, r, seed = drs
        x, u = _point_and_tangent(m, d, r, seed)
        # R_x(0) = x
        np.testing.assert_allclose(m.retract(x, jnp.zeros_like(x), kind), x,
                                   atol=1e-5)
        # result on-manifold
        y = m.retract(x, u, kind)
        assert float(jnp.max(m.check(y))) < 1e-5
        # first-order agreement R_x(tu) = x + tu + O(t^2): generous
        # second-order constant shared by all kinds here
        for t in (0.5, 0.25):
            resid = float(jnp.linalg.norm(m.retract(x, t * u, kind) - (x + t * u)))
            unorm2 = float(jnp.sum((t * u) ** 2))
            assert resid <= 8.0 * unorm2 + 1e-5, (d, r, seed, t)

    run()


@pytest.mark.parametrize("name", sorted(G.REGISTRY))
def test_tangent_projection_idempotent_and_kills_base(name):
    m = G.get(name)

    @given(dims())
    @settings(**SET)
    def run(drs):
        d, r, seed = drs
        x = m.rand(jax.random.PRNGKey(seed), d, r)
        g = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, r))
        u = m.tangent_project(x, g)
        np.testing.assert_allclose(m.tangent_project(x, u), u, atol=1e-5)
        if name != "euclidean":   # flat space has no vertical component
            np.testing.assert_allclose(m.tangent_project(x, x), 0.0, atol=1e-5)
        # rand lands on-manifold; project is idempotent
        assert float(jnp.max(m.check(x))) < 1e-4
        np.testing.assert_allclose(m.project(x), x, atol=1e-4)

    run()


@pytest.mark.parametrize("name", sorted(G.REGISTRY))
def test_consensus_mean_and_dist(name):
    m = G.get(name)
    x = m.rand(jax.random.PRNGKey(3), 24, 6)
    same = jnp.broadcast_to(x[None], (5, 24, 6))
    xhat = m.consensus_mean(same)
    assert float(jnp.max(m.check(xhat))) < 1e-4
    if name != "grassmann":   # a Grassmann mean is any representative basis
        np.testing.assert_allclose(xhat, x, atol=1e-4)
    assert float(m.dist(xhat, x)) < 1e-2
    # perturbed cloud: mean is on-manifold and close to the cloud
    pert = x[None] + 0.01 * jax.random.normal(jax.random.PRNGKey(4), (8, 24, 6))
    xs = jax.vmap(m.project)(pert)
    xhat = m.consensus_mean(xs)
    assert float(jnp.max(m.check(xhat))) < 1e-4
    assert float(m.dist(xhat, x)) < 0.1


@pytest.mark.parametrize("name", ["stiefel", "grassmann"])
def test_manifold_algebra_runs_at_highest_precision(name):
    """Every matmul of the retraction, projection and feasibility check is
    asked for at HIGHEST precision: a TPU's default (one bf16 pass) leaves
    x^T x off by ~0.1 at smollm widths."""
    m = G.REGISTRY[name]
    x = m.rand(jax.random.PRNGKey(0), 64, 16)
    u = m.tangent_project(x, jax.random.normal(jax.random.PRNGKey(1),
                                                (64, 16)))

    def step(x, u):
        y = m.retract(x, m.tangent_project(x, u))
        return y, m.check(y)

    jaxpr = jax.make_jaxpr(step)(x, u)
    dots = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(eqn.params["precision"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert dots
    hi = jax.lax.Precision.HIGHEST
    assert all(p == (hi, hi) for p in dots), dots


def test_cayley_any_step_size_stays_feasible():
    """The CG normal-equation solve converges for ANY ||u|| (the Neumann
    fixed point needs ||u|| < 1 and documents so)."""
    x = M.random_stiefel(jax.random.PRNGKey(0), 32, 8)
    g = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    u = M.tangent_project(x, g)
    u = u / jnp.linalg.norm(u)
    for scale in (0.1, 1.0, 4.0):
        y = M.retract_cayley(x, scale * u)
        assert float(M.stiefel_error(y)) < 1e-4, scale
    # neumann agrees on small steps
    us = 0.05 * u / jnp.linalg.norm(u)
    np.testing.assert_allclose(M.retract_cayley(x, us, solver="neumann"),
                               M.retract_cayley(x, us), atol=1e-5)


def test_unknown_retraction_name_rejected_by_optimizer():
    """Per-leaf resolution falls back silently, so DecentralizedGDA must
    reject globally-unknown names (typo guard)."""
    from repro.core import DRGDA, GDAHyper, GossipSpec
    from repro.core.minimax import MinimaxProblem

    prob = MinimaxProblem(loss_fn=lambda x, y, b: jnp.sum(x["w"]),
                          project_y=lambda y: y, stiefel_mask={"w": True})
    spec = GossipSpec(topology="ring", n_nodes=4)
    with pytest.raises(ValueError, match="unknown retraction"):
        DRGDA(prob, spec, GDAHyper(retraction="polr"))
    for ok in ("polar", "qr", "cayley", "polar_fused", "normalize", "add"):
        DRGDA(prob, spec, GDAHyper(retraction=ok))


def test_manifold_map_from_paths_tall_filter_is_per_geometry():
    """d >= r is a Stiefel/Grassmann requirement; norm-constraint
    geometries must constrain wide leaves too."""
    params = {"wide": jnp.zeros((4, 16)), "tall": jnp.zeros((16, 4)),
              "vec": jnp.zeros((8,))}
    st = G.manifold_map_from_paths(params, lambda p: True, "stiefel")
    assert st["wide"] is G.EUCLIDEAN and st["tall"] is G.STIEFEL
    ob = G.manifold_map_from_paths(params, lambda p: True, "oblique")
    assert ob["wide"] is G.OBLIQUE and ob["tall"] is G.OBLIQUE
    assert ob["vec"] is G.EUCLIDEAN


def test_registry_dispatch_and_unknown_kind():
    assert G.get("stiefel") is G.STIEFEL
    with pytest.raises(ValueError):
        G.get("klein-bottle")
    x = M.random_stiefel(jax.random.PRNGKey(0), 8, 2)
    with pytest.raises(ValueError):
        M.retract(x, jnp.zeros_like(x), "bogus")
    # resolve_retraction falls back to each geometry's default
    assert G.get("oblique").resolve_retraction("cayley") == "normalize"
    assert G.get("euclidean").resolve_retraction("polar_fused") == "add"


# ---------------------------------------------------------------------------
# fused Pallas retraction vs the eigh oracle
# ---------------------------------------------------------------------------


FUSED_CASES = [(16, 4), (64, 16), (100, 7), (200, 9), (256, 128)]


@pytest.mark.parametrize("d,r", FUSED_CASES)
@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_fused_retract_matches_eigh_oracle(d, r, impl):
    x = M.random_stiefel(jax.random.PRNGKey(d + r), d, r)
    g = 0.3 * jax.random.normal(jax.random.PRNGKey(d + r + 1), (d, r))
    want = M.retract_polar(x, M.tangent_project(x, g), method="eigh")
    got = ops.fused_retract(x, g, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    assert float(M.stiefel_error(got)) < 1e-4


def test_fused_retract_node_stacked_batch():
    x = M.random_stiefel(jax.random.PRNGKey(0), 48, 8, batch=(6,))
    g = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (6, 48, 8))
    want = M.retract_polar(x, M.tangent_project(x, g), method="eigh")
    got = ops.fused_retract(x, g, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_polar_fused_hyper_runs_drgda():
    """GDAHyper(retraction="polar_fused") must produce (ref-dispatch) steps
    equivalent to the unfused polar path within NS/fp32 tolerance."""
    from repro.core import DRGDA, GDAHyper, GossipSpec
    from repro.core.gda import broadcast_to_nodes
    from repro.core.minimax import MinimaxProblem, project_simplex

    d, r, grp, n = 12, 3, 3, 6
    a = jnp.asarray(np.random.RandomState(0).randn(grp, d, d), jnp.float32)
    a = (a + jnp.swapaxes(a, 1, 2)) / 2

    def loss_fn(x, y, batch):
        lg = -jnp.einsum("dr,gde,er->g", x["w"], a + batch, x["w"])
        return jnp.dot(y, lg) - jnp.sum((y - 1.0 / grp) ** 2)

    prob = MinimaxProblem(loss_fn=loss_fn, project_y=project_simplex,
                          stiefel_mask={"w": True})
    x0 = broadcast_to_nodes({"w": M.random_stiefel(jax.random.PRNGKey(5), d, r)}, n)
    y0 = jnp.full((n, grp), 1.0 / grp)
    batches = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (n, grp, d, d))

    finals = []
    for kind in ("polar", "polar_fused"):
        opt = DRGDA(prob, GossipSpec(topology="ring", n_nodes=n),
                    GDAHyper(alpha=0.5, beta=0.05, eta=0.2, retraction=kind))
        state = opt.init(x0, y0, batches)
        step = opt.make_step(donate=False)
        for _ in range(25):
            state, _ = step(state, batches)
        assert float(M.stiefel_error(state.x["w"]).max()) < 1e-4
        finals.append(state.x["w"])
    np.testing.assert_allclose(np.asarray(finals[0]), np.asarray(finals[1]),
                               atol=2e-4)


# ---------------------------------------------------------------------------
# ManifoldMap: legacy bool masks, strings, instances; Product manifold
# ---------------------------------------------------------------------------


def test_manifold_map_accepts_legacy_bool_mask():
    from repro.core.minimax import MinimaxProblem

    prob = MinimaxProblem(loss_fn=lambda x, y, b: jnp.sum(x["w"]) + jnp.sum(y),
                          project_y=lambda y: y,
                          stiefel_mask={"w": True, "bias": False})
    assert prob.manifold_map["w"] is G.STIEFEL
    assert prob.manifold_map["bias"] is G.EUCLIDEAN
    assert prob.stiefel_mask == {"w": True, "bias": False}


def test_manifold_map_strings_and_instances_normalize():
    mmap = G.as_manifold_map({"a": "grassmann", "b": G.OBLIQUE, "c": False})
    assert mmap["a"] is G.GRASSMANN
    assert mmap["b"] is G.OBLIQUE
    assert mmap["c"] is G.EUCLIDEAN
    assert G.bool_mask(mmap) == {"a": False, "b": False, "c": False}


def test_rgrads_match_legacy_stiefel_path():
    """The geometry-generic rgrads must equal the historical masked path."""
    from repro.core.minimax import MinimaxProblem, apply_masked

    def loss_fn(x, y, b):
        return jnp.sum(x["w"] * b) + jnp.sum(x["e"] ** 2) + jnp.sum(y)

    prob = MinimaxProblem(loss_fn=loss_fn, project_y=lambda y: y,
                          stiefel_mask={"w": True, "e": False})
    x = {"w": M.random_stiefel(jax.random.PRNGKey(0), 10, 3),
         "e": jnp.ones((4, 2))}
    batch = jnp.ones((10, 3))
    rgx, _ = prob.rgrads(x, jnp.zeros((3,)), batch)
    gx, _ = prob.grads(x, jnp.zeros((3,)), batch)
    want = apply_masked({"w": True, "e": False}, x, gx,
                        stiefel_fn=M.tangent_project, eucl_fn=lambda _, g: g)
    for k in x:
        np.testing.assert_array_equal(np.asarray(rgx[k]), np.asarray(want[k]))


def test_product_manifold_ops():
    pm = G.Product({"w": "stiefel", "s": "sphere", "e": "euclidean"})
    key = jax.random.PRNGKey(0)
    like = {"w": jnp.zeros((16, 4)), "s": jnp.zeros((6, 2)),
            "e": jnp.zeros((3, 3))}
    x = pm.rand(key, like)
    assert float(pm.check(x)) < 1e-4
    g = jax.tree.map(lambda l: jnp.ones_like(l), like)
    u = pm.tangent_project(x, g)
    y = pm.retract(x, jax.tree.map(lambda t: 0.1 * t, u), kind="qr")
    assert float(pm.check(y)) < 1e-4
    assert float(pm.dist(x, x)) < 1e-2
    # feasible_init respects every leaf's geometry
    raw = jax.tree.map(lambda l: l + 3.0, g)
    init = pm.feasible_init(raw)
    assert float(pm.check(init)) < 1e-4
    np.testing.assert_array_equal(np.asarray(init["e"]), np.asarray(raw["e"]))


def test_validate_manifold_generalizes_validate_stiefel():
    from repro.core.minimax import validate_manifold, validate_stiefel

    x = {"w": M.random_stiefel(jax.random.PRNGKey(0), 12, 4),
         "e": jnp.full((3, 3), 7.0)}
    assert float(validate_stiefel(x, {"w": True, "e": False})) < 1e-5
    assert float(validate_manifold(x, {"w": "stiefel", "e": "euclidean"})) < 1e-5
    bad = {"w": x["w"] * 2.0, "e": x["e"]}
    assert float(validate_manifold(bad, {"w": "stiefel", "e": "euclidean"})) > 0.1
    ob = {"w": G.OBLIQUE.rand(jax.random.PRNGKey(1), 9, 5), "e": x["e"]}
    assert float(validate_manifold(ob, {"w": "oblique", "e": False})) < 1e-5


# ---------------------------------------------------------------------------
# Grassmann robust PCA: the new workload end to end (short run)
# ---------------------------------------------------------------------------


def test_robust_pca_drgda_converges_and_beats_pca_worst_case():
    from repro.core import DRGDA, GDAHyper, GossipSpec
    from repro.core.gda import broadcast_to_nodes
    from repro.core.metric import convergence_metric
    from repro.objectives import robust_pca as rp

    d, r, m, n, rho = 16, 2, 16, 6, 0.5
    problem = rp.make_robust_pca_problem(rho=rho)
    batches, basis = rp.make_batches(jax.random.PRNGKey(1), n, m, d, r,
                                     outlier_frac=0.1, outlier_scale=1.5)
    x0 = broadcast_to_nodes(
        {"w": G.GRASSMANN.rand(jax.random.PRNGKey(0), d, r)}, n)
    opt = DRGDA(problem, GossipSpec(topology="ring", n_nodes=n),
                GDAHyper(alpha=0.5, beta=0.1, eta=0.3))
    state = opt.init(x0, rp.init_y(n, m), batches)
    step = opt.make_step(donate=False)
    met0 = convergence_metric(problem, state.x, state.y, batches)
    for _ in range(400):
        state, _ = step(state, batches)
    met = convergence_metric(problem, state.x, state.y, batches)
    assert float(met["M_t"]) < 0.05 * float(met0["M_t"])
    assert float(met["stiefel_residual"]) < 1e-4       # representative on St
    assert float(G.GRASSMANN.dist(state.x["w"][0], basis)) < 0.6

    def phi(x):
        ystar = rp.robust_pca_y_star({"w": x}, batches, rho=rho)
        res = jnp.mean(jax.vmap(lambda z: rp.residuals(x, z))(batches["z"]), 0)
        return float(jnp.dot(ystar, res) - rho * jnp.sum((ystar - 1 / m) ** 2))

    z = np.asarray(batches["z"].reshape(-1, d))
    pca = jnp.asarray(np.linalg.eigh(z.T @ z)[1][:, -r:])
    assert phi(state.x["w"][0]) <= phi(pca) + 1e-4


def test_robust_pca_objective_is_basis_invariant():
    """A Grassmann objective: rotating the basis within the span must not
    change the loss (what the quotient geometry buys)."""
    from repro.objectives import robust_pca as rp

    d, r, m = 12, 3, 10
    x = G.GRASSMANN.rand(jax.random.PRNGKey(0), d, r)
    q = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(1), (r, r)))[0]
    z = jax.random.normal(jax.random.PRNGKey(2), (m, d))
    y = jnp.full((m,), 1.0 / m)
    l1 = rp.robust_pca_loss({"w": x}, y, {"z": z}, rho=0.5)
    l2 = rp.robust_pca_loss({"w": x @ q}, y, {"z": z}, rho=0.5)
    np.testing.assert_allclose(float(l1), float(l2), atol=1e-5)
