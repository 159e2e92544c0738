"""Distribution-layer tests on a small in-process device mesh.

conftest note: these tests spawn with XLA_FLAGS forcing 8 host devices via
a subprocess-free trick — jax device count is locked at first use, so this
module must NOT run in the same process as tests that already initialized
jax with 1 device.  We therefore only test logic that doesn't need devices
(spec mapping, plans) here, plus mesh-dependent paths guarded by the
actual device count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.models import model_zoo as zoo
from repro.parallel import sharding as shd


def _abstract_mesh():
    """16x16 (data, model) AbstractMesh."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


class TestSpecMapping:
    def test_duplicate_mesh_axis_dropped(self):
        # MoE expert tensors: (EXPERT, EMBED, MLP) — expert FSDPs over
        # (model, data); mlp's 'model' is then already taken -> None
        ps = shd.spec_to_pspec(("expert", "embed", "mlp"))
        assert tuple(ps) == (("model", "data"), None, None)
        # without the FSDP rule, plain TP mapping
        ps2 = shd.spec_to_pspec(("expert", "embed", "mlp"),
                                {**shd.RULES, "expert": "model"})
        assert tuple(ps2) == ("model", None, None)

    def test_standard_mappings(self):
        assert tuple(shd.spec_to_pspec(("embed", "mlp"))) == (None, "model")
        assert tuple(shd.spec_to_pspec(("vocab", "embed"))) == \
            ("model", None)
        assert tuple(shd.spec_to_pspec(("stack", "embed", "heads"))) == \
            (None, None, "model")

    def test_param_specs_cover_every_leaf(self):
        for arch in ("qwen2-0.5b", "deepseek-v2-236b", "zamba2-7b",
                     "whisper-tiny"):
            cfg = get_config(arch, smoke=True)
            params = zoo.init_params(cfg, jax.random.PRNGKey(0),
                                     abstract=True)
            specs = zoo.param_specs(cfg)
            p_leaves = jax.tree.leaves(params)
            s_leaves = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, tuple))
            assert len(p_leaves) == len(s_leaves)
            for p, s in zip(p_leaves, s_leaves):
                assert len(s) == p.ndim, (s, p.shape)

    def test_head_padding_in_param_shapes(self):
        cfg = get_config("qwen2-0.5b")           # 14 heads, head_pad=16
        params = zoo.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
        group = params["body"]["stack"]
        assert group["attn"]["wq"]["w"].shape == \
            (24, cfg.d_model, 16 * cfg.resolved_head_dim)
        assert group["attn"]["wk"]["w"].shape == \
            (24, cfg.d_model, 2 * cfg.resolved_head_dim)   # kv NOT padded

    def test_divisible_fixup_replicates_odd_vocab(self):
        # whisper vocab 51865 isn't divisible by 16 -> replicated
        mesh = _abstract_mesh()
        cfg = get_config("whisper-tiny")
        abs_p = zoo.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
        specs = zoo.param_specs(cfg)
        sh = shd.param_shardings(specs, mesh, abs_p)
        # table (51865, 384): vocab would map to model; fixup drops it
        emb = sh["embed"]["table"]
        assert tuple(emb.spec) in ((), (None,), (None, None))
        # qwen2 (151936 % 16 == 0) keeps the vocab sharding
        cfg2 = get_config("qwen2-0.5b")
        sh2 = shd.param_shardings(
            zoo.param_specs(cfg2), mesh,
            zoo.init_params(cfg2, jax.random.PRNGKey(0), abstract=True))
        assert sh2["embed"]["table"].spec[0] == "model"


class TestCacheShardings:
    def _mesh(self):
        return _abstract_mesh()

    def test_attention_cache_seq_sharded(self):
        mesh = self._mesh()
        cache = {"k": jax.ShapeDtypeStruct((128, 32768, 2, 128),
                                           jnp.bfloat16),
                 "pos": jax.ShapeDtypeStruct((128, 32768), jnp.int32)}
        sh = shd.cache_shardings(cache, mesh, 128)
        assert sh["k"].spec[1] == "model"        # flash-decode layout
        assert sh["pos"].spec[1] == "model"

    def test_ssm_state_heads_sharded(self):
        mesh = self._mesh()
        cache = {"ssm": jax.ShapeDtypeStruct((128, 112, 64, 64),
                                             jnp.float32)}
        sh = shd.cache_shardings(cache, mesh, 128)
        assert sh["ssm"].spec[1] == "model"

    def test_long_context_batch1_seq_data_sharded(self):
        mesh = self._mesh()
        cache = {"k": jax.ShapeDtypeStruct((1, 524288, 8, 240),
                                           jnp.bfloat16)}
        sh = shd.cache_shardings(cache, mesh, 1)
        spec = sh["k"].spec
        assert spec[0] is None                    # batch 1: not sharded
        assert spec[1] is not None                # sequence carries data/SP


class TestCellSupport:
    def test_supported_counts(self):
        from repro.configs import cell_is_supported, list_archs
        total = ok = 0
        for a in list_archs():
            for s in SHAPES.values():
                total += 1
                ok += cell_is_supported(get_config(a), s)[0]
        assert total == 40 and ok == 34           # 6 documented skips


class TestMoELoadBalance:
    def test_balanced_vs_collapsed_router(self):
        from repro.configs.base import MoEConfig
        from repro.models import moe as M

        e, d, t = 8, 16, 256
        cfg = MoEConfig(num_experts=e, experts_per_token=2, d_ff_expert=8)
        # positive activations so the "collapsed" router (one hot column)
        # deterministically wins the argmax
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, t, d)))
        balanced = {"router": jnp.zeros((d, e), jnp.float32) +
                    0.01 * jax.random.normal(jax.random.PRNGKey(1), (d, e))}
        collapsed = {"router": jnp.zeros((d, e), jnp.float32)
                     .at[:, 0].set(10.0)}
        lb = float(M.load_balance_loss(balanced, x, cfg))
        lc = float(M.load_balance_loss(collapsed, x, cfg))
        assert lb < 2.0          # near-uniform routing -> loss ~ 1
        assert lc > e * 0.9      # total collapse -> loss ~ E

    def test_moe_capacity_drops_are_bounded(self):
        """With a generous capacity factor no tokens should drop: routed
        output must be nonzero for every token."""
        from repro.configs.base import MoEConfig
        from repro.models import layers as L
        from repro.models import moe as M

        cfg = MoEConfig(num_experts=4, experts_per_token=2, d_ff_expert=16,
                        capacity_factor=4.0)
        mk = L.ParamMaker(jax.random.PRNGKey(0), dtype=jnp.float32)
        params = M.make_moe(mk, "moe", 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
        out = M.moe_ffn(params, x, cfg)
        norms = jnp.linalg.norm(out.reshape(-1, 16), axis=-1)
        assert float(jnp.min(norms)) > 0.0
