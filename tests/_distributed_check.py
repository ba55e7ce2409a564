"""Subprocess body for multi-device CPU tests (run with
XLA_FLAGS=--xla_force_host_platform_device_count=8).

Asserts:
  1. sharded train_step loss == unsharded loss (llama reduced, mesh 2x2)
  2. MoE EP (a2a over model axis) == mesh-free reference path
  3. MoE decode (replicated+psum path) == mesh-free reference path
  4. compressed_psum mean ≈ true mean within int8 quantisation error
  5. multi-pod mesh (2,2,2) train_step compiles & runs
  6. elastic checkpoint restore onto a different mesh
  7. GPipe pipeline == sequential execution
  8. sharded sDTW (ppermute boundary-column exchange) == numpy oracle
  9. sharded top-K heap == single-process streamer bitwise
  10. sharded spans + top-K span heap (start-pointer lane through the
      ppermute carry) == single-process bitwise, both suppression modes
  11. sharded streaming session (per-device chunk streams through the
      ppermute carry, carries handed back between feeds) == single-process
      StreamSession bitwise, both suppression modes + snapshot/restore
  12. 2D (dp, mp) mesh == 1D mesh == single-device engine bitwise for
      batch / top-K both modes / spans / streaming; schedule invariance
      across n_micro and ragged tails; bounded pipeline-cache compile
      counts

``--sdtw-mesh dp,mp`` runs only the sDTW sections (8-11 equivalents) on
that mesh shape and prints DISTRIBUTED_SDTW_OK — the CI distributed job
sweeps (1,8) / (2,4) / (4,2) through it.
"""
import argparse
import os

assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]

_ap = argparse.ArgumentParser()
_ap.add_argument("--sdtw-mesh", default=None,
                 help="dp,mp — run only the sDTW sections on that mesh")
SDTW_MESH = _ap.parse_args().sdtw_mesh
if SDTW_MESH is not None:
    SDTW_MESH = tuple(int(x) for x in SDTW_MESH.split(","))

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

assert len(jax.devices()) == 8
KEY = jax.random.PRNGKey(0)

if SDTW_MESH is None:
    from repro import checkpoint as ckpt
    from jax import shard_map
    from repro.configs import get_arch
    from repro.distributed import Axes
    from repro.distributed.collectives import compressed_psum
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import tree_shardings
    from repro.models import RunConfig, init_lm, loss_fn
    from repro.models.moe import moe_mlp
    from repro.optim import OptConfig
    from repro.train import TrainConfig, init_train_state, make_train_step

    RUN = RunConfig(remat="none", attn_mode="dense",
                    compute_dtype=jnp.float32)

    # --- 1. sharded == unsharded train loss ------------------------------
    cfg = get_arch("llama3.2-1b").reduced()
    params = init_lm(cfg, KEY)
    batch = {"tokens": jax.random.randint(KEY, (4, 16), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (4, 16), 0, cfg.vocab)}
    loss_ref, _ = loss_fn(cfg, params, batch, None, RUN)

    mesh = make_mesh((2, 2), ("data", "model"))
    axes = Axes.from_mesh(mesh)
    with mesh:
        loss_sh, _ = jax.jit(
            lambda p, b: loss_fn(cfg, p, b, axes, RUN))(params, batch)
    np.testing.assert_allclose(float(loss_sh), float(loss_ref), rtol=2e-5)
    print("1 OK: sharded loss matches", float(loss_sh))

    # --- 2/3. MoE EP paths == reference ----------------------------------
    # capacity_factor high enough that nothing drops: capacity dropping is
    # per-source-shard in the EP path vs global in the reference path, so
    # the paths are only bitwise-comparable in the no-drop regime.
    mcfg = dataclasses.replace(get_arch("qwen3-moe-30b-a3b").reduced(),
                               n_experts=4, topk=2, capacity_factor=4.0)
    mp = init_lm(mcfg, KEY)
    moe_params = jax.tree.map(lambda p: p[0], mp["blocks"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, mcfg.d_model),
                          jnp.float32)
    out_ref, aux_ref = moe_mlp(moe_params, mcfg, x, None)
    with mesh:
        out_a2a, aux_a2a = jax.jit(
            lambda p, v: moe_mlp(p, mcfg, v, axes))(moe_params, x)
    np.testing.assert_allclose(np.asarray(out_a2a), np.asarray(out_ref),
                               atol=2e-5)
    # aux is computed per shard then pmean'd: mean of per-shard E·Σf_e·p_e
    # is a (standard) approximation of the global aux — close, not
    # identical.
    np.testing.assert_allclose(float(aux_a2a), float(aux_ref), rtol=0.1)
    print("2 OK: MoE a2a path matches reference")

    xd = x[:, :1]  # S=1 → replicated/psum decode path
    out_ref_d, _ = moe_mlp(moe_params, mcfg, xd, None)
    with mesh:
        out_rep, _ = jax.jit(
            lambda p, v: moe_mlp(p, mcfg, v, axes))(moe_params, xd)
    np.testing.assert_allclose(np.asarray(out_rep), np.asarray(out_ref_d),
                               atol=2e-5)
    print("3 OK: MoE replicated decode path matches reference")

    # --- 4. compressed psum ----------------------------------------------
    vals = jax.random.normal(jax.random.PRNGKey(2), (8, 64), jnp.float32)
    flat_mesh = make_mesh((8,), ("d",))
    with flat_mesh:
        got = jax.jit(shard_map(
            lambda v: compressed_psum(v[0], "d")[None],
            mesh=flat_mesh, in_specs=P("d", None), out_specs=P("d", None),
            check_vma=False))(vals)
    want = jnp.mean(vals, axis=0)
    scale = float(jnp.max(jnp.abs(vals))) / 127.0
    assert float(jnp.max(jnp.abs(got[0] - want))) < scale
    print("4 OK: compressed_psum within quantisation error")

    # --- 4b. pad_heads path (kv=2 heads on a 4-way model axis) ------------
    mesh24 = make_mesh((2, 4), ("data", "model"))
    axes24 = Axes.from_mesh(mesh24)
    assert cfg.n_kv_heads % 4 != 0   # exercises the padding branch
    run_pad = dataclasses.replace(RUN, pad_heads=True)
    with mesh24:
        loss_pad, _ = jax.jit(
            lambda p, b: loss_fn(cfg, p, b, axes24, run_pad))(params, batch)
    np.testing.assert_allclose(float(loss_pad), float(loss_ref), rtol=2e-5)
    print("4b OK: pad_heads path matches reference", float(loss_pad))

    # --- 5. multi-pod mesh train step ------------------------------------
    pod_mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pod_axes = Axes.from_mesh(pod_mesh)
    assert pod_axes.dp == ("pod", "data")
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3))
    state = init_train_state(cfg, params, tcfg)
    with pod_mesh:
        shardings = tree_shardings(jax.eval_shape(lambda: state), pod_axes,
                                   "train")
        state_sh = jax.tree.map(jax.device_put, state, shardings)
        step = jax.jit(make_train_step(cfg, RUN, tcfg, pod_axes))
        state2, metrics = step(state_sh, batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss_ref),
                               rtol=2e-5)
    print("5 OK: multi-pod train step, loss", float(metrics["loss"]))

    # --- 6. elastic restore onto a different mesh ------------------------
    tmp = tempfile.mkdtemp()
    ckpt.save(tmp, 0, state2, extra={"step": 0})
    new_mesh = make_mesh((4, 2), ("data", "model"))
    new_axes = Axes.from_mesh(new_mesh)
    with new_mesh:
        new_sh = tree_shardings(jax.eval_shape(lambda: state), new_axes,
                                "train")
        restored, _, _ = ckpt.restore(tmp, state, shardings=new_sh)
        step2 = jax.jit(make_train_step(cfg, RUN, tcfg, new_axes))
        state3, metrics3 = step2(restored, batch)
    assert np.isfinite(float(metrics3["loss"]))
    print("6 OK: elastic restore onto 4x2 mesh, loss",
          float(metrics3["loss"]))

    # --- 7. pipeline parallelism == sequential ---------------------------
    from repro.distributed.pipeline import pipeline_apply, split_stages

    L, D = 8, 16
    keys = jax.random.split(jax.random.PRNGKey(3), L)
    layer_params = {"w": jnp.stack([
        0.3 * jax.random.normal(k, (D, D)) for k in keys])}

    def block(lp, x):
        return jnp.tanh(x @ lp["w"])

    xm = jax.random.normal(jax.random.PRNGKey(4), (6, 4, D))  # 6 microbatch
    # sequential reference
    seq = xm
    for i in range(L):
        seq = jax.vmap(lambda x: block({"w": layer_params["w"][i]}, x))(seq)

    pp_mesh = make_mesh((4,), ("stage",))
    staged = split_stages(layer_params, 4)
    with pp_mesh:
        got = pipeline_apply(block, staged, xm, pp_mesh, "stage")
    np.testing.assert_allclose(np.asarray(got), np.asarray(seq), atol=1e-5)
    print("7 OK: GPipe pipeline matches sequential execution")

# --- sDTW sections (8-12): shared check body, parameterized over mesh -----
from repro.core import sdtw as engine_sdtw
from repro.core import stream as open_stream
from repro.core.sdtw import sdtw_chunked
from repro.core.sdtw_ref import sdtw_ref
from repro.distributed import get_mesh
from repro.distributed.sdtw_sharded import (_cache_size,
                                            clear_pipeline_cache,
                                            default_mesh)
from repro.stream import ShardedStreamSession

rng8 = np.random.default_rng(42)


def check_sdtw(sdtw_mesh, tag):
    """Batch, top-K (both exclusion modes), spans, and a sharded stream on
    ``sdtw_mesh`` — every lane bitwise against the single-device engine
    (and the batch lane against the numpy oracle). The body every mesh
    shape must pass unchanged."""
    # batch vs oracle, int32 bitwise + float32 tolerance
    for dtype in (np.int32, np.float32):
        qs8 = rng8.integers(-40, 40, (8, 6)).astype(dtype)
        r8 = rng8.integers(-40, 40, 97).astype(dtype)  # 97: ragged over 8
        got8 = np.asarray(engine_sdtw(jnp.asarray(qs8), jnp.asarray(r8),
                                      mesh=sdtw_mesh, chunk=8))
        want8 = np.array([sdtw_ref(qs8[i], r8) for i in range(8)])
        if dtype == np.int32:
            np.testing.assert_array_equal(got8, want8)
        else:
            np.testing.assert_allclose(got8, want8, rtol=1e-5)
    print(f"{tag}: batch matches oracle")

    # top-K merge (heap rides the systolic carry)
    qs9 = rng8.integers(-40, 40, (8, 6)).astype(np.int32)
    r9 = rng8.integers(-40, 40, 97).astype(np.int32)
    sd, sp = engine_sdtw(jnp.asarray(qs9), jnp.asarray(r9), mesh=sdtw_mesh,
                         chunk=8, top_k=3, excl_zone=4)
    cd, cp = sdtw_chunked(jnp.asarray(qs9), jnp.asarray(r9), chunk=8,
                          top_k=3, excl_zone=4)
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(cd))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(cp))
    d9, p9 = engine_sdtw(jnp.asarray(qs9), jnp.asarray(r9), mesh=sdtw_mesh,
                         chunk=8, return_positions=True)
    np.testing.assert_array_equal(np.asarray(d9), np.asarray(cd)[:, 0])
    np.testing.assert_array_equal(np.asarray(p9), np.asarray(cp)[:, 0])
    print(f"{tag}: top-K heap matches single-process bitwise")

    # spans (start-pointer lane) + top-K spans, both suppression modes
    qs10 = rng8.integers(-8, 8, (8, 6)).astype(np.int32)  # tie-heavy range
    r10 = rng8.integers(-8, 8, 97).astype(np.int32)
    sd10, ss10, se10 = engine_sdtw(jnp.asarray(qs10), jnp.asarray(r10),
                                   mesh=sdtw_mesh, chunk=8,
                                   return_spans=True)
    cd10, cs10, ce10 = sdtw_chunked(jnp.asarray(qs10), jnp.asarray(r10),
                                    chunk=8, return_spans=True)
    np.testing.assert_array_equal(np.asarray(sd10), np.asarray(cd10))
    np.testing.assert_array_equal(np.asarray(ss10), np.asarray(cs10))
    np.testing.assert_array_equal(np.asarray(se10), np.asarray(ce10))
    for mode in ("end", "span"):
        tk_s = engine_sdtw(jnp.asarray(qs10), jnp.asarray(r10),
                           mesh=sdtw_mesh, chunk=8, top_k=3, excl_zone=4,
                           excl_mode=mode, return_spans=True)
        tk_c = sdtw_chunked(jnp.asarray(qs10), jnp.asarray(r10), chunk=8,
                            top_k=3, excl_zone=4, excl_mode=mode,
                            return_spans=True)
        for a, b in zip(tk_s, tk_c):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(f"{tag}: spans + top-K span heap match single-process bitwise")

    # streaming session == single-process StreamSession
    qs11 = rng8.integers(-8, 8, (8, 6)).astype(np.int32)
    r11 = rng8.integers(-8, 8, 97).astype(np.int32)

    sh11 = open_stream(qs11, mesh=sdtw_mesh, chunk=4)
    sp11 = open_stream(qs11, chunk=4)
    for off in range(0, 97, 17):
        sh11.feed(r11[off:off + 17])
        sp11.feed(r11[off:off + 17])
    np.testing.assert_array_equal(np.asarray(sh11.results().distances),
                                  np.asarray(sp11.results().distances))
    np.testing.assert_array_equal(
        np.asarray(sh11.results().distances),
        np.asarray(engine_sdtw(jnp.asarray(qs11), jnp.asarray(r11),
                               chunk=4, impl="chunked")))

    for mode in ("end", "span"):
        sh = open_stream(qs11, mesh=sdtw_mesh, chunk=4, top_k=3,
                         excl_zone=4, excl_mode=mode, return_spans=True)
        sp = open_stream(qs11, chunk=4, top_k=3, excl_zone=4,
                         excl_mode=mode, return_spans=True)
        for off in range(0, 97, 13):
            sh.feed(r11[off:off + 13])
            sp.feed(r11[off:off + 13])
        a, b = sh.results(), sp.results()
        for x, y in ((a.distances, b.distances), (a.starts, b.starts),
                     (a.positions, b.positions)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"mode={mode}")
        tk = sdtw_chunked(jnp.asarray(qs11), jnp.asarray(r11), chunk=4,
                          top_k=3, excl_zone=4, excl_mode=mode,
                          return_spans=True)
        np.testing.assert_array_equal(np.asarray(a.distances),
                                      np.asarray(tk[0]))
        np.testing.assert_array_equal(np.asarray(a.starts),
                                      np.asarray(tk[1]))
        np.testing.assert_array_equal(np.asarray(a.positions),
                                      np.asarray(tk[2]))

    # Snapshot mid-stream, restore, keep feeding: bitwise-identical tail.
    sh = open_stream(qs11, mesh=sdtw_mesh, chunk=4, top_k=3,
                     return_spans=True)
    sh.feed(r11[:64])
    sh2 = ShardedStreamSession.restore(sh.snapshot(), mesh=sdtw_mesh)
    sh.feed(r11[64:])
    sh2.feed(r11[64:])
    a, b = sh.results(), sh2.results()
    for x, y in ((a.distances, b.distances), (a.starts, b.starts),
                 (a.positions, b.positions)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print(f"{tag}: sharded stream matches single-process session bitwise, "
          f"both modes + snapshot/restore")


if SDTW_MESH is not None:
    check_sdtw(get_mesh(SDTW_MESH), f"sdtw mesh {SDTW_MESH}")
    print("DISTRIBUTED_SDTW_OK")
    raise SystemExit(0)

# --- 8-11. sDTW on the classic 1-D ("ref",) mesh --------------------------
ref_mesh = default_mesh("ref")
assert ref_mesh.shape["ref"] == 8
check_sdtw(ref_mesh, "8-11 OK (1D ref mesh)")

# --- 12. 2D (dp, mp) mesh == 1D == single-device; schedule invariance -----
mesh24 = get_mesh((2, 4))
check_sdtw(mesh24, "12 OK (2D (2,4) mesh)")

# Schedule invariance: bitwise-identical int32 results across n_micro
# (including 2*ndev) and a ragged tail (nq=17 not divisible by anything
# swept), on 1D and 2D meshes.
qs12 = rng8.integers(-40, 40, (17, 6)).astype(np.int32)
r12 = rng8.integers(-40, 40, 97).astype(np.int32)
want12 = np.asarray(sdtw_chunked(jnp.asarray(qs12), jnp.asarray(r12),
                                 chunk=8, top_k=3, excl_zone=4,
                                 return_spans=True))
mesh1d = get_mesh((8,))
for m12, micros in ((mesh1d, (1, 2, 8, 16)), (mesh24, (1, 2, 4, 8))):
    for nm in micros:
        got12 = np.asarray(engine_sdtw(
            jnp.asarray(qs12), jnp.asarray(r12), mesh=m12, chunk=8,
            n_micro=nm, top_k=3, excl_zone=4, return_spans=True))
        np.testing.assert_array_equal(
            got12, want12, err_msg=f"mesh={m12.shape} n_micro={nm}")
print("12 OK: schedule-invariant across n_micro sweeps + ragged tail")

# Bounded pipeline cache: same config compiles once; keyed on the mesh
# fingerprint, not the live Mesh object.
clear_pipeline_cache()
assert _cache_size() == 0
engine_sdtw(jnp.asarray(qs12), jnp.asarray(r12), mesh=mesh24, chunk=8)
n_after_one = _cache_size()
assert n_after_one == 1, n_after_one
engine_sdtw(jnp.asarray(qs12), jnp.asarray(r12), mesh=mesh24, chunk=8)
assert _cache_size() == n_after_one          # cache hit, no recompile
engine_sdtw(jnp.asarray(qs12), jnp.asarray(r12), mesh=get_mesh((2, 4)),
            chunk=8)
assert _cache_size() == n_after_one          # equal fingerprint, same entry
print("12 OK: pipeline cache bounded + fingerprint-keyed")

print("DISTRIBUTED_ALL_OK")
