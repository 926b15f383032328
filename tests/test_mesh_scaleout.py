"""PR 10: data-parallel mesh scale-out.

Covers the ``shard_map`` train step end to end on forced host-platform
devices (conftest sets ``--xla_force_host_platform_device_count=8``):

  * ``launch/mesh.py`` helpers on the modern ``jax.sharding.Mesh`` API;
  * loader shard splitting with -1 tail padding (non-dividing batches);
  * the sampler's masked-seed handling;
  * compression round-trips, error feedback, and compressed-psum parity;
  * ``MeshTrainer`` grad/loss parity vs the single-device accumulation
    oracle, single-trace behaviour, and the golden dispatch audit;
  * checkpointed elastic resize (4 -> 2 devices, bit-identical params).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data.data import Data
from repro.data.loader import (Batch, NeighborLoader, split_seed_shards,
                               stack_batches)
from repro.data.sampler import NeighborSampler
from repro.distributed import compression as comp_lib
from repro.launch.mesh import (HOST_DEVICE_FLAG, data_parallel_mesh,
                               host_device_flag, make_mesh)
from repro.launch.train import MeshTrainer
from repro.train import optimizer as opt_lib

FEAT, HIDDEN = 32, 16


def _graph(n=256, e=2048, seed=0):
    rng = np.random.default_rng(seed)
    return Data(x=rng.standard_normal((n, FEAT)).astype(np.float32),
                edge_index=np.stack([rng.integers(0, n, e),
                                     rng.integers(0, n, e)]),
                y=rng.standard_normal(n).astype(np.float32))


def _params(seed=1):
    rng = np.random.default_rng(seed)
    return {"w1": jnp.asarray(rng.standard_normal((FEAT, HIDDEN)) * 0.1,
                              jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((HIDDEN, 1)) * 0.1,
                              jnp.float32)}


def _loss_fn(force_pallas=False):
    from repro.nn.gnn.conv import gcn_norm
    interpret = True if force_pallas else None

    def loss_fn(params, batch):
        ew, _ = gcn_norm(batch.edge_index, batch.num_nodes,
                         add_self_loops=False)
        h = jax.nn.relu(batch.edge_index.matmul(
            batch.x @ params["w1"], edge_weight=ew,
            force_pallas=force_pallas, interpret=interpret))
        out = batch.edge_index.matmul(h @ params["w2"], edge_weight=ew,
                                      force_pallas=force_pallas,
                                      interpret=interpret)
        err = ((out[batch.seed_slots] - batch.y[:, None]) ** 2).sum(axis=-1)
        mask = batch.seed_mask.astype(jnp.float32)
        return (err * mask).sum(), mask.sum()

    return loss_fn


def _loader(data, shards, *, n_seeds=24, batch_size=8, **kw):
    kw.setdefault("prefill_ell", False)
    return NeighborLoader(data, data, num_neighbors=[4, 2],
                          batch_size=batch_size,
                          input_nodes=np.arange(n_seeds), drop_last=False,
                          shards=shards, seed=0, **kw)


def _oracle_step(loss_fn, cfg, d):
    """Single-device gradient accumulation over the same shards."""

    @jax.jit
    def step(state, stacked):
        def total(p):
            ls = ws = 0.0
            for i in range(d):
                shard = jax.tree_util.tree_map(lambda l, i=i: l[i], stacked)
                l, w = loss_fn(p, shard)
                ls, ws = ls + l, ws + w
            return ls, ws
        (loss_sum, weight), grads = jax.value_and_grad(
            total, has_aux=True)(state.params)
        w = jnp.maximum(weight, 1e-12)
        grads = jax.tree_util.tree_map(lambda g: g / w, grads)
        state, metrics = opt_lib.apply_updates(state, grads, cfg)
        metrics["loss"] = loss_sum / w
        return state, metrics

    return step


def _max_param_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# --------------------------------------------------------------- mesh.py
class TestMeshHelpers:
    def test_forced_host_devices_visible(self):
        # conftest must have forced 8 devices before jax initialised
        assert len(jax.devices()) >= 8

    def test_make_mesh_shape_and_axes(self):
        mesh = make_mesh((2, 2), ("data", "model"))
        assert mesh.shape == {"data": 2, "model": 2}
        assert mesh.axis_names == ("data", "model")

    def test_make_mesh_sub_mesh_over_prefix(self):
        # a 2-device mesh inside an 8-device process: the scaling sweep's
        # core requirement the stale all-device helpers couldn't express
        mesh = make_mesh((2,), ("data",))
        assert mesh.devices.size == 2
        assert list(mesh.devices.ravel()) == list(jax.devices()[:2])

    def test_make_mesh_shape_axes_mismatch(self):
        with pytest.raises(ValueError, match="disagree"):
            make_mesh((2, 2), ("data",))

    def test_make_mesh_too_few_devices_names_flag(self):
        with pytest.raises(ValueError) as ei:
            make_mesh((1024,), ("data",))
        msg = str(ei.value)
        assert HOST_DEVICE_FLAG in msg and "1024" in msg

    def test_host_device_flag(self):
        assert host_device_flag(4) == f"{HOST_DEVICE_FLAG}=4"

    def test_data_parallel_mesh(self):
        mesh = data_parallel_mesh(4, axis_name="data")
        assert mesh.axis_names == ("data",)
        assert mesh.devices.size == 4


# ------------------------------------------------------- loader sharding
class TestLoaderSharding:
    def test_split_even(self):
        parts = split_seed_shards(np.arange(8), None, 4)
        assert len(parts) == 4
        assert all(len(s) == 2 for s, _ in parts)
        np.testing.assert_array_equal(
            np.concatenate([s for s, _ in parts]), np.arange(8))

    def test_split_non_dividing_pads_minus_one(self):
        parts = split_seed_shards(np.arange(6), None, 4)
        seeds = np.concatenate([s for s, _ in parts])
        assert len(seeds) == 8
        np.testing.assert_array_equal(seeds[:6], np.arange(6))
        np.testing.assert_array_equal(seeds[6:], [-1, -1])

    def test_split_pads_time_with_zero(self):
        t = np.arange(5, dtype=np.int64) + 100
        parts = split_seed_shards(np.arange(5), t, 2)
        times = np.concatenate([tt for _, tt in parts])
        assert len(times) == 6
        assert times[-1] == 0

    def test_split_invalid_shards(self):
        with pytest.raises(ValueError, match="shards"):
            split_seed_shards(np.arange(4), None, 0)

    def test_stacked_batch_shapes(self):
        data = _graph()
        batches = list(_loader(data, shards=4))
        assert len(batches) == 3
        for b in batches:
            assert isinstance(b, Batch)
            for leaf in jax.tree_util.tree_leaves(b):
                assert leaf.shape[0] == 4

    def test_tail_batch_padded_not_dropped(self):
        # 20 seeds / batch 8 -> last batch has 4 seeds over 4 shards:
        # 1 per shard, no padding; 18 seeds -> last batch 2 over 4 shards,
        # 2 shards get a -1 pad seed each. Neither crashes nor drops seeds.
        data = _graph()
        batches = list(_loader(data, shards=4, n_seeds=18))
        assert len(batches) == 3
        tail = batches[-1]
        seed_ids = np.asarray(tail.n_id)[
            np.arange(4)[:, None], np.asarray(tail.seed_slots)]
        real = seed_ids[seed_ids >= 0]
        assert sorted(real.tolist()) == [16, 17]
        assert (seed_ids < 0).sum() == 2  # the two pad seeds

    def test_seed_mask_and_label_padding(self):
        data = _graph()
        batches = list(_loader(data, shards=4, n_seeds=18))
        tail = batches[-1]
        for i in range(4):
            shard = jax.tree_util.tree_map(lambda l, i=i: l[i], tail)
            mask = np.asarray(shard.seed_mask)
            y = np.asarray(shard.y)
            # padded seeds contribute zero labels and a False mask
            assert (y[~mask] == 0).all()
            sid = np.asarray(shard.n_id)[np.asarray(shard.seed_slots)]
            np.testing.assert_array_equal(mask, sid >= 0)

    def test_health_counts_global_batches(self):
        data = _graph()
        plain = _loader(data, shards=1)
        list(plain)
        sharded = _loader(data, shards=4)
        list(sharded)
        assert plain.health == sharded.health
        assert sharded.health["batches"] == 3
        assert sharded.health["skipped_batches"] == 0

    def test_sharded_equals_concat_of_plain_shards(self):
        # shard i of the stacked batch == a plain loader run over the same
        # seed slice (same sampler seed): sharding only regroups seeds
        data = _graph()
        stacked = next(iter(_loader(data, shards=2, n_seeds=8)))
        shard0 = jax.tree_util.tree_map(lambda l: l[0], stacked)
        plain = next(iter(_loader(data, shards=1, n_seeds=4, batch_size=4)))
        np.testing.assert_array_equal(np.asarray(shard0.n_id),
                                      np.asarray(plain.n_id))
        np.testing.assert_allclose(np.asarray(shard0.x),
                                   np.asarray(plain.x))

    def test_stack_batches_roundtrip(self):
        data = _graph()
        plain = list(_loader(data, shards=1, n_seeds=8, batch_size=4))
        stacked = stack_batches(plain)
        assert stacked.x.shape == (2,) + plain[0].x.shape
        back = jax.tree_util.tree_map(lambda l: l[1], stacked)
        np.testing.assert_array_equal(np.asarray(back.n_id),
                                      np.asarray(plain[1].n_id))


# ------------------------------------------------------- sampler pad seeds
class TestSamplerPadSeeds:
    def test_minus_one_seed_keeps_layout(self):
        data = _graph()
        sampler = NeighborSampler(data, [3, 2], seed=0)
        out = sampler.sample(np.array([5, -1, 7]))
        assert out.node[0] == -1                      # null sink
        np.testing.assert_array_equal(out.node[1:4], [5, -1, 7])
        np.testing.assert_array_equal(out.seed_slots, [1, 2, 3])

    def test_minus_one_seed_expands_nothing(self):
        data = _graph()
        sampler = NeighborSampler(data, [4], seed=0)
        out = sampler.sample(np.array([-1]))
        assert (out.edge < 0).all()                   # all edges padding
        assert (out.node[2:] == -1).all()             # no neighbors found

    def test_no_dedup_corruption_from_pad(self):
        # the old slot_of[seeds] wrote slot ids through index -1 onto the
        # LAST global node; sampling that node afterwards must still work
        data = _graph()
        sampler = NeighborSampler(data, [2], seed=0)
        sampler.sample(np.array([3, -1]))
        n_last = sampler.csr.num_rows - 1
        out = sampler.sample(np.array([n_last, 3]))
        np.testing.assert_array_equal(out.node[1:3], [n_last, 3])
        assert (sampler._slot_of == -1).all()         # lookup fully reset


# ------------------------------------------------------------ compression
class TestCompression:
    def test_int8_roundtrip_bound(self, rng):
        x = jnp.asarray(rng.standard_normal(257).astype(np.float32))
        q, scale = comp_lib.quantize_int8(x)
        err = jnp.abs(comp_lib.dequantize_int8(q, scale) - x)
        assert float(err.max()) <= float(scale) * 0.5001 + 1e-7

    def test_topk_ratio_one_lossless(self, rng):
        x = jnp.asarray(rng.standard_normal((13, 7)).astype(np.float32))
        v, i = comp_lib.topk_compress(x, x.size)
        back = comp_lib.topk_decompress(v, i, x.shape)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   rtol=0, atol=0)

    def test_topk_partial_keeps_largest(self, rng):
        x = jnp.asarray(np.array([0.1, -5.0, 0.2, 3.0], np.float32))
        v, i = comp_lib.topk_compress(x, 2)
        back = np.asarray(comp_lib.topk_decompress(v, i, x.shape))
        np.testing.assert_allclose(back, [0.0, -5.0, 0.0, 3.0])

    @pytest.mark.parametrize("method,ratio", [("int8", 1.0), ("topk", 0.25)])
    def test_error_feedback_telescopes(self, rng, method, ratio):
        # sum of dequantised payloads + final residual == sum of raw grads
        grads = [
            {"w": jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)}
            for _ in range(5)]
        residual = comp_lib.init_residual(grads[0])
        applied = jnp.zeros((6, 5))
        for g in grads:
            payload, residual = comp_lib.compress_grads(
                g, residual, method=method, ratio=ratio)
            applied = applied + comp_lib.decompress_grads(
                payload, g, method=method)["w"]
        total = sum(g["w"] for g in grads)
        np.testing.assert_allclose(np.asarray(applied + residual["w"]),
                                   np.asarray(total), rtol=1e-5, atol=1e-5)

    def test_compressed_allreduce_matches_psum(self, rng):
        # topk at ratio 1.0 is lossless: the all_gather+decompress-sum path
        # must agree with a plain psum to <= 1e-5
        from jax.sharding import PartitionSpec as P

        mesh = data_parallel_mesh(4)
        g = jnp.asarray(rng.standard_normal((4, 8, 3)), jnp.float32)
        r = jnp.zeros((4, 8, 3), jnp.float32)

        def body(g, r):
            lg = {"w": g[0]}
            summed, _ = comp_lib.compressed_allreduce(
                lg, {"w": r[0]}, axis_name="data", method="topk", ratio=1.0)
            return summed["w"]

        got = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P(), check_vma=False))(g, r)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(g.sum(axis=0)),
                                   rtol=1e-5, atol=1e-5)

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError, match="method"):
            comp_lib.compress_grads({"w": jnp.zeros(3)},
                                    {"w": jnp.zeros(3)}, method="fft")

    def test_payload_nbytes_orders(self):
        like = {"w": jnp.zeros((100, 100))}
        raw = 100 * 100 * 4
        assert comp_lib.payload_nbytes(like, method="int8") < raw
        assert comp_lib.payload_nbytes(
            like, method="topk", ratio=0.01) < raw // 10


# ------------------------------------------------------------ mesh trainer
@pytest.fixture(scope="module")
def trained_pair():
    """(mesh_state, oracle_state, trainer, batches, state0, cfg): one
    4-device epoch stepped by both the sharded and the oracle step."""
    data = _graph()
    loss_fn = _loss_fn()
    cfg = opt_lib.OptConfig(lr=1e-2, warmup_steps=1, total_steps=50)
    state0 = opt_lib.init_state(_params(), cfg)
    mesh = data_parallel_mesh(4)
    trainer = MeshTrainer(loss_fn, cfg, mesh=mesh)
    batches = list(_loader(data, shards=4, n_seeds=22))  # tail: 6 seeds
    oracle = _oracle_step(loss_fn, cfg, 4)
    s_mesh = s_orc = state0
    losses = []
    for b in batches:
        s_mesh, m = trainer.step(s_mesh, b)
        s_orc, mo = oracle(s_orc, b)
        losses.append((float(m["loss"]), float(mo["loss"])))
    return s_mesh, s_orc, trainer, batches, state0, cfg, losses


class TestMeshTrainer:
    def test_grad_parity_4dev(self, trained_pair):
        s_mesh, s_orc = trained_pair[0], trained_pair[1]
        assert _max_param_diff(s_mesh.params, s_orc.params) <= 1e-5
        assert _max_param_diff(s_mesh.mu, s_orc.mu) <= 1e-5

    def test_loss_parity(self, trained_pair):
        losses = trained_pair[6]
        assert all(abs(a - b) <= 1e-5 for a, b in losses)

    def test_single_trace_across_batches(self, trained_pair):
        assert trained_pair[2].trace_count == 1

    def test_wrong_leading_dim_rejected(self, trained_pair):
        trainer, batches, state0 = (trained_pair[2], trained_pair[3],
                                    trained_pair[4])
        shard = jax.tree_util.tree_map(lambda l: l[:2], batches[0])
        with pytest.raises(ValueError, match="shards=4"):
            trainer.step(state0, shard)

    def test_golden_dispatch_audit(self):
        # forced-Pallas loss: the sharded step must show the same kernel
        # set as the single-device step, exactly one fused psum, zero
        # oracle fallbacks. Abstract trace only (no interpret execution).
        from repro.analysis.dispatch import audit_report
        data = _graph()
        cfg = opt_lib.OptConfig(lr=1e-2, warmup_steps=1, total_steps=50)
        state0 = opt_lib.init_state(_params(), cfg)
        trainer = MeshTrainer(_loss_fn(force_pallas=True), cfg,
                              mesh=data_parallel_mesh(4))
        batch = next(iter(_loader(data, shards=4, prefill_ell=True)))
        rep = audit_report(trainer._step.__wrapped__, state0, batch)
        rep.assert_fused(expect_kernels=("_spmm_ell_kernel",),
                         min_launches=2,
                         expect_collectives={"psum": 1})
        assert rep.oracle_fallbacks == 0

    def test_compressed_topk_full_ratio_parity(self, trained_pair):
        # the compressed all-reduce machinery at ratio=1.0 must reproduce
        # the raw-psum step to <= 1e-5 (mechanism parity)
        batches, state0, cfg = (trained_pair[3], trained_pair[4],
                                trained_pair[5])
        s_orc = trained_pair[1]
        tr = MeshTrainer(_loss_fn(), cfg, mesh=data_parallel_mesh(4),
                         compression="topk", compression_ratio=1.0)
        s = state0
        for b in batches:
            s, _ = tr.step(s, b)
        assert _max_param_diff(s.params, s_orc.params) <= 1e-5
        assert tr.trace_count == 1

    def test_compressed_int8_steps_and_converges(self, trained_pair):
        batches, state0, cfg = (trained_pair[3], trained_pair[4],
                                trained_pair[5])
        tr = MeshTrainer(_loss_fn(), cfg, mesh=data_parallel_mesh(4),
                         compression="int8")
        s = state0
        first = last = None
        for _ in range(3):
            for b in batches:
                s, m = tr.step(s, b)
                first = first if first is not None else float(m["loss"])
                last = float(m["loss"])
        assert np.isfinite(last) and last < first

    def test_collective_bytes_compressed_below_raw(self, trained_pair):
        from repro.launch import jaxpr_stats
        batches, state0, cfg = (trained_pair[3], trained_pair[4],
                                trained_pair[5])
        raw_tr = trained_pair[2]
        raw = jaxpr_stats.analyze_jaxpr(
            raw_tr.step_jaxpr(state0, batches[0]))
        int8_tr = MeshTrainer(_loss_fn(), cfg, mesh=data_parallel_mesh(4),
                              compression="int8")
        int8 = jaxpr_stats.analyze_jaxpr(
            int8_tr.step_jaxpr(state0, batches[0]))
        assert raw["collective_bytes"] > 0
        assert int8["collective_bytes"] < raw["collective_bytes"]

    def test_invalid_compression_rejected(self, trained_pair):
        cfg = trained_pair[5]
        with pytest.raises(ValueError, match="compression"):
            MeshTrainer(_loss_fn(), cfg, mesh=data_parallel_mesh(2),
                        compression="zip")

    def test_needs_1d_mesh(self, trained_pair):
        cfg = trained_pair[5]
        with pytest.raises(ValueError, match="1-D"):
            MeshTrainer(_loss_fn(), cfg, mesh=make_mesh((2, 2),
                                                        ("data", "model")))


# ------------------------------------------------- checkpoint + elastic
class TestElasticResize:
    def test_resize_4_to_2_bit_identical(self, tmp_path, trained_pair):
        s_mesh, trainer, state0 = (trained_pair[0], trained_pair[2],
                                   trained_pair[4])
        trainer.save(str(tmp_path), 7, s_mesh)
        small = MeshTrainer(_loss_fn(), trained_pair[5],
                            mesh=data_parallel_mesh(2))
        restored, step = small.restore(str(tmp_path), state0)
        assert step == 7
        for a, b in zip(jax.tree_util.tree_leaves(s_mesh),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resized_trainer_continues(self, tmp_path, trained_pair):
        s_mesh, state0, cfg = (trained_pair[0], trained_pair[4],
                               trained_pair[5])
        trained_pair[2].save(str(tmp_path), 3, s_mesh)
        small = MeshTrainer(_loss_fn(), cfg, mesh=data_parallel_mesh(2),
                            compression="topk", compression_ratio=1.0)
        restored, _ = small.restore(str(tmp_path), state0)
        assert small._residual is None  # error feedback restarts on resize
        data = _graph()
        batch = next(iter(_loader(data, shards=2)))
        s, m = small.step(restored, batch)
        assert np.isfinite(float(m["loss"]))
        assert int(s.step) == int(restored.step) + 1
