import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""SPMD correctness checks (run as a subprocess with 8 host devices).

Covers: device-path TAM & two-phase collective write vs oracle; TAM
coalescing stats; hierarchical two-layer psum / compressed psum /
two-layer all_to_all; moe_sharded vs dense-path equivalence; sharded
decode attention vs flash reference. Exits nonzero on any failure.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

FAILURES = []


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    if not ok:
        FAILURES.append(name)


def main():
    from repro.core import (IOConfig, contiguous_layout, make_tam_write,
                            make_twophase_write)
    from repro.core.tam import make_tam_read
    from repro.core.twophase import make_twophase_read, write_reference
    from repro.core.hierarchical import (compressed_psum,
                                         two_layer_all_to_all,
                                         two_layer_psum)

    mesh = make_mesh((2, 2, 2), ("node", "lagg", "lmem"))
    P_ranks, REQ_CAP, DATA_CAP, FILE_LEN = 8, 8, 64, 256
    layout = contiguous_layout(FILE_LEN, 2)
    rng = np.random.default_rng(0)
    slots = rng.permutation(FILE_LEN // 8)
    spr = len(slots) // P_ranks
    O = np.full((P_ranks, REQ_CAP), 2**31 - 1, np.int32)
    L = np.zeros((P_ranks, REQ_CAP), np.int32)
    C = np.zeros(P_ranks, np.int32)
    D = np.zeros((P_ranks, DATA_CAP), np.int32)
    for p in range(P_ranks):
        mine = np.sort(slots[p * spr:(p + 1) * spr])
        offs = (mine * 8).astype(np.int32)
        lens = rng.integers(1, 9, size=len(mine)).astype(np.int32)
        O[p, :len(offs)], L[p, :len(lens)], C[p] = offs, lens, len(offs)
        D[p, :lens.sum()] = rng.integers(1, 999, size=lens.sum())
    ref = write_reference(layout, O, L, C, D)
    cfg = IOConfig(req_cap=32, data_cap=DATA_CAP, coalesce_cap=32)

    f, s = jax.jit(make_twophase_write(mesh, layout, cfg))(O, L, C, D)
    check("twophase_write", np.array_equal(np.asarray(f).reshape(-1), ref))
    f, s = jax.jit(make_tam_write(mesh, layout, cfg))(O, L, C, D)
    check("tam_write", np.array_equal(np.asarray(f).reshape(-1), ref))
    check("tam_no_drops", int(s["dropped_requests"]) == 0
          and int(s["dropped_elems"]) == 0)
    f, s = jax.jit(make_tam_write(mesh, layout, cfg, use_kernels=True))(
        O, L, C, D)
    check("tam_write_kernels", np.array_equal(np.asarray(f).reshape(-1),
                                              ref))

    rd = jax.jit(make_tam_read(mesh, layout, cfg))
    got = rd(O, L, C, jnp.asarray(ref).reshape(2, -1))
    ok = all(np.array_equal(np.asarray(got)[p][:L[p].sum()],
                            D[p][:L[p].sum()]) for p in range(P_ranks))
    check("tam_read", ok)
    rd2 = jax.jit(make_twophase_read(mesh, layout, cfg))
    got = rd2(O, L, C, jnp.asarray(ref).reshape(2, -1))
    ok = all(np.array_equal(np.asarray(got)[p][:L[p].sum()],
                            D[p][:L[p].sum()]) for p in range(P_ranks))
    check("twophase_read", ok)

    # block pattern: coalescing fires
    Ob = np.full((8, 8), 2**31 - 1, np.int32)
    Lb = np.zeros((8, 8), np.int32)
    for p in range(8):
        Ob[p, :4] = np.arange(4, dtype=np.int32) * 8 + p * 32
        Lb[p, :4] = 8
    Cb = np.full(8, 4, np.int32)
    Db = (np.arange(8 * DATA_CAP, dtype=np.int32).reshape(8, -1) % 97) + 1
    Db[:, 32:] = 0
    refb = write_reference(layout, Ob, Lb, Cb, Db)
    f, s = jax.jit(make_tam_write(mesh, layout, cfg))(Ob, Lb, Cb, Db)
    check("tam_block_write", np.array_equal(np.asarray(f).reshape(-1), refb))
    check("tam_block_coalesce",
          int(s["requests_after_coalesce"]) * 4
          <= int(s["requests_before_coalesce"]))

    # ---- hierarchical collectives ------------------------------------
    mesh2 = make_mesh((2, 4), ("pod", "ici"))
    x = jnp.asarray(rng.normal(size=(8, 33)).astype(np.float32))
    r2 = jax.jit(jax.shard_map(
        lambda xs: two_layer_psum(xs.reshape(33), "ici", "pod"),
        mesh=mesh2, in_specs=P(("pod", "ici")), out_specs=P(),
        check_vma=False))(x)
    check("two_layer_psum",
          np.allclose(np.asarray(r2), np.asarray(x.sum(0)), atol=1e-4))

    outc, nres = jax.jit(jax.shard_map(
        lambda xs, res: compressed_psum(xs.reshape(33), res.reshape(33),
                                        "ici", "pod"),
        mesh=mesh2, in_specs=(P(("pod", "ici")), P(("pod", "ici"))),
        out_specs=(P(), P(("pod", "ici"))), check_vma=False))(
            x, jnp.zeros_like(x))
    rel = (np.abs(np.asarray(outc) - np.asarray(x.sum(0))).max()
           / np.abs(np.asarray(x.sum(0))).max())
    check("compressed_psum_int8", rel < 5e-2)
    check("compressed_psum_residual_nonzero",
          float(jnp.abs(nres).sum()) > 0)

    xa = jnp.arange(8 * 8 * 5, dtype=jnp.int32).reshape(8, 8 * 5)
    ra = jax.jit(jax.shard_map(
        lambda xs: two_layer_all_to_all(xs.reshape(8, 5), "ici", "pod"),
        mesh=mesh2, in_specs=P(("pod", "ici")), out_specs=P(("pod", "ici")),
        check_vma=False))(xa)
    ref_a = np.transpose(np.asarray(xa).reshape(8, 8, 5),
                         (1, 0, 2)).reshape(8, 8 * 5)
    check("two_layer_all_to_all",
          np.array_equal(np.asarray(ra).reshape(8, -1), ref_a))

    # ---- moe_sharded vs dense ----------------------------------------
    from dataclasses import replace as dreplace
    from repro import configs
    from repro.models import layers as ML
    from repro.models import transformer as MT
    from repro.models.config import reduced
    from repro.models.sharding import ShardingPlan, unsharded

    mesh3 = make_mesh((2, 4), ("data", "model"))
    cfg_m = reduced(configs.get("llama4_maverick"))
    cfg_m = dreplace(cfg_m, moe=dreplace(cfg_m.moe, capacity_factor=4.0),
                     d_model=32, vocab=256)
    key = jax.random.PRNGKey(0)
    moe_p = ML.init_moe(key, cfg_m, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
    dense_out, dense_aux = ML.moe(moe_p, x, cfg_m, unsharded())
    plan3 = ShardingPlan(mesh=mesh3, data_axes=("data",),
                         model_axis="model", shard_seq=True)
    sh_out, sh_aux = jax.jit(
        lambda p, xx: ML.moe(p, xx, cfg_m, plan3))(moe_p, x)
    check("moe_sharded_matches_dense",
          np.allclose(np.asarray(sh_out), np.asarray(dense_out),
                      rtol=2e-4, atol=2e-4))
    # per-shard aux is an E[me_loc*ce_loc] approximation of the global
    # E[me]*E[ce] product (standard distributed-MoE practice); they agree
    # in expectation, not exactly.
    check("moe_aux_close",
          abs(float(sh_aux) - float(dense_aux)) < 0.25 * float(dense_aux)
          + 0.05)

    plan3d = ShardingPlan(mesh=mesh3, data_axes=("data",),
                          model_axis="model", shard_seq=False)
    sh_out2, _ = jax.jit(
        lambda p, xx: ML.moe(p, xx, cfg_m, plan3d))(moe_p, x[:, :1])
    dense2, _ = ML.moe(moe_p, x[:, :1], cfg_m, unsharded())
    check("moe_decode_path_matches_dense",
          np.allclose(np.asarray(sh_out2), np.asarray(dense2),
                      rtol=2e-4, atol=2e-4))

    # ---- sharded decode attention vs flash ---------------------------
    B, S, HQ, HKV, HD = 4, 64, 8, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, HQ, HD))
    kc = jax.random.normal(jax.random.PRNGKey(3), (B, S, HKV, HD))
    vc = jax.random.normal(jax.random.PRNGKey(4), (B, S, HKV, HD))
    pos = jnp.int32(37)
    ref_o = ML.flash_attention(q, kc, vc, causal=False, window=None,
                               logit_cap=None, q_offset=pos,
                               kv_len=pos + 1)
    got = jax.jit(lambda q, k, v: ML.decode_attention_sharded(
        q, k, v, cache_pos=pos, window=None, logit_cap=None,
        plan=plan3d))(q, kc, vc)
    check("decode_attention_sharded",
          np.allclose(np.asarray(got).reshape(B, 1, HQ, HD),
                      np.asarray(ref_o), rtol=2e-3, atol=2e-3))

    # full train step under the production mesh partitioning (2x4)
    cfg_t = reduced(configs.get("glm4_9b"))
    plan_t = ShardingPlan(mesh=mesh3, data_axes=("data",),
                          model_axis="model", shard_seq=True)
    params = MT.init_params(jax.random.PRNGKey(5), cfg_t,
                            dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(key, (4, 16), 0, cfg_t.vocab),
             "labels": jax.random.randint(key, (4, 16), 0, cfg_t.vocab)}
    loss_sharded = jax.jit(
        lambda p: MT.loss_fn(p, cfg_t, batch, plan_t))(params)
    loss_local = MT.loss_fn(params, cfg_t, batch, unsharded())
    check("sharded_loss_matches_local",
          abs(float(loss_sharded) - float(loss_local)) < 2e-3)

    print(f"{len(FAILURES)} failures", flush=True)
    raise SystemExit(1 if FAILURES else 0)


if __name__ == "__main__":
    main()
