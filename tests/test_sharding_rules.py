"""The sharding substrate (tier-1, CPU, in-process — no engine
compiles): parallel/sharding.py is the SINGLE source of logical-axis
rules shared by train/ and inference.

- grep-level lint: no second PartitionSpec rule table survives outside
  parallel/ (the ISSUE-8 dedup satellite — train and ops now import
  spec_for/tree_shardings instead of hardcoding physical specs);
- the decode-specific rules map attention heads, KV heads, MLP hidden
  and vocab/embedding onto the tp axis;
- tree_shardings translates a boxed decode-model tree (params AND the
  KV-cache variables) into per-leaf NamedShardings on a tp mesh;
- decode_mesh / assert_tp_compatible / infer_serving_tp plumbing;
- hlo_probe.collective_stats parses counts and bytes from HLO text.
"""
import os

import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec

PKG_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'skypilot_tpu')


class TestNoDuplicateRuleTables:
    """Thin wrappers over skylint's sharding-containment checker
    (skypilot_tpu/analysis/sharding.py) — the AST re-implementation of
    the grep lints that used to live here, so exactly ONE
    implementation of each rule exists. tests/test_skylint.py carries
    the fixture coverage (seeded violations, alias rebinding, comment
    immunity)."""

    def test_sharding_containment_checker_clean(self):
        """PartitionSpec axis-name strings and quoted collective axes
        stay inside parallel/; layouts flow through spec_for /
        constrain / tree_shardings and collectives take their axis as
        a parameter."""
        from skypilot_tpu import analysis
        result = analysis.run_lint(select=['sharding-containment'])
        assert not result.unwaived, '\n'.join(
            str(f) for f in result.unwaived)

    def test_exactly_one_rule_table_in_parallel(self):
        """Exactly one logical-axis rule table exists, and it lives in
        parallel/sharding.py (AST assignment sites, not text scan)."""
        from skypilot_tpu.analysis import core as skylint_core
        from skypilot_tpu.analysis import sharding as sharding_checker
        tree = skylint_core.ProjectTree(PKG_ROOT)
        sites = sharding_checker.rule_table_sites(tree)
        assert [rel for _repo_rel, rel, _line in sites] == \
            ['parallel/sharding.py'], sites


class TestDecodeRules:

    def test_tp_axis_covers_decode_dims(self):
        """The dims tensor-parallel decode shards — attention heads,
        KV heads (the cache axis), MLP hidden, vocab/embedding — all
        map to `tp`."""
        from skypilot_tpu.parallel import spec_for
        assert spec_for('heads') == PartitionSpec('tp')
        assert spec_for('kv_heads') == PartitionSpec('tp')
        assert spec_for('mlp') == PartitionSpec('tp')
        assert spec_for('vocab') == PartitionSpec('tp')
        # The paged pool leaf layout: (blocks, block, kv_heads, dim).
        assert spec_for(None, None, 'kv_heads', None) == \
            PartitionSpec(None, None, 'tp', None)

    def test_trainer_and_inference_share_the_helper(self):
        """The moved helper is what both sides call — no local copy of
        the rule application survives in train/ or models/."""
        import inspect

        from skypilot_tpu.models import inference
        from skypilot_tpu.train import trainer
        assert 'tree_shardings' in inspect.getsource(trainer)
        assert 'tree_shardings' in inspect.getsource(inference)
        # And neither re-applies the rules by hand.
        for mod in (trainer, inference):
            assert 'logical_to_mesh_sharding' not in \
                inspect.getsource(mod), mod.__name__


class TestMeshPlumbing:

    def test_decode_mesh_shape(self):
        from skypilot_tpu.parallel import decode_mesh
        mesh = decode_mesh(2)
        assert dict(mesh.shape)['tp'] == 2
        assert all(s == 1 for a, s in dict(mesh.shape).items()
                   if a != 'tp')

    def test_decode_mesh_rejects_bad_tp(self):
        from skypilot_tpu.parallel import decode_mesh
        with pytest.raises(ValueError):
            decode_mesh(0)
        with pytest.raises(ValueError):
            decode_mesh(len(jax.devices()) + 1)

    def test_assert_tp_compatible(self):
        from skypilot_tpu.models import get_config
        cfg = get_config('test-tiny')      # 4 heads, 2 kv heads
        cfg.assert_tp_compatible(1)
        cfg.assert_tp_compatible(2)
        with pytest.raises(ValueError, match='num_kv_heads'):
            cfg.assert_tp_compatible(4)    # heads divide, kv heads don't

    def test_infer_serving_tp(self):
        from skypilot_tpu.models import get_config
        from skypilot_tpu.models.inference import infer_serving_tp
        tiny = get_config('test-tiny')
        assert infer_serving_tp(tiny, 1) == 1
        assert infer_serving_tp(tiny, 8) == 2   # kv_heads=2 caps it
        big = get_config('llama3-8b')           # kv_heads=8
        assert infer_serving_tp(big, 8) == 8
        assert infer_serving_tp(big, 6) == 2    # 6 % 4 != 0; 2 divides

    def test_engine_rejects_non_tp_mesh(self):
        """Serving meshes are tp-only for now: a dp/fsdp axis > 1 must
        refuse up front (GSPMD would silently pad the 2-slot batch)."""
        from skypilot_tpu.models import get_config
        from skypilot_tpu.models.inference import (
            _validate_serving_mesh)
        from skypilot_tpu.parallel import MeshConfig, build_mesh
        mesh = build_mesh(MeshConfig(fsdp=2), jax.devices()[:2])
        with pytest.raises(ValueError, match='tensor parallelism only'):
            _validate_serving_mesh(get_config('test-tiny'), mesh)

    def test_tree_shardings_places_cache_on_tp(self):
        """The KV-cache variables' logical metadata translates to
        kv-head sharding on a decode mesh — params and cache flow
        through ONE helper."""
        import dataclasses

        import jax.numpy as jnp
        from flax import linen as nn

        from skypilot_tpu.models import get_config
        from skypilot_tpu.models.transformer import Transformer
        from skypilot_tpu.parallel import decode_mesh, tree_shardings
        cfg = dataclasses.replace(get_config('test-tiny'), decode=True,
                                  remat=False)
        model = Transformer(cfg)
        mesh = decode_mesh(2)
        abstract = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32)))
        shardings = nn.unbox(tree_shardings(mesh, abstract))
        leaves = jax.tree.leaves(shardings)
        assert leaves and all(isinstance(s, NamedSharding)
                              for s in leaves)
        # At least one cache leaf and one param leaf shard on tp.
        cache_specs = [s.spec for s in
                       jax.tree.leaves(shardings['cache'])]
        assert any('tp' in jax.tree.leaves(list(sp))
                   for sp in cache_specs), cache_specs
        param_specs = [s.spec for s in
                       jax.tree.leaves(shardings['params'])]
        assert any('tp' in jax.tree.leaves(list(sp))
                   for sp in param_specs), param_specs


class TestHloProbe:

    HLO = '''
  %add.1 = f32[4,64]{1,0} add(%a, %b)
  %all-reduce.3 = f32[4,1,64]{2,1,0} all-reduce(%x), replica_groups={}
  %ar2 = (f32[8]{0}, bf16[2,2]{1,0}) all-reduce(%y, %z)
  %ag = f32[4,512]{1,0} all-gather(%w), dimensions={1}
  %start = f32[16]{0} collective-permute-start(%p)
  %done = f32[16]{0} collective-permute-done(%start)
  %ars = (f32[8]{0}, f32[8]{0}) all-reduce-start(%q)
  %ard = f32[8]{0} all-reduce-done(%ars)
'''

    def test_counts_and_bytes(self):
        from skypilot_tpu.parallel import hlo_probe
        stats = hlo_probe.collective_stats(self.HLO)
        assert stats['all_reduce'] == 3
        # 4*1*64*4 + (8*4 + 2*2*2) + 8*4 = 1024 + 40 + 32 — the async
        # -start tuple's mirrored (operand-alias, result) halves count
        # ONCE, not summed.
        assert stats['all_reduce_bytes'] == 1096
        assert stats['all_gather'] == 1
        assert stats['all_gather_bytes'] == 4 * 512 * 4
        # start/done pairs count once.
        assert stats['collective_permute'] == 1
        assert stats['total'] == 5
        assert stats['total_bytes'] == (
            1096 + 4 * 512 * 4 + 16 * 4)

    def test_empty(self):
        from skypilot_tpu.parallel import hlo_probe
        stats = hlo_probe.collective_stats('%r = f32[2] add(%a, %b)')
        assert stats['total'] == 0 and stats['total_bytes'] == 0


@pytest.mark.sharded
@pytest.mark.deadline(900)
class TestShardedRestore:
    """The PR-7 named follow-up: restore_params_only(mesh=decode_mesh)
    deserializes a train checkpoint DIRECTLY into the serving mesh's
    tree_shardings placement — a tp>1 engine's weights never
    materialize whole on device 0 on their way through _place_params.
    One subprocess run on 8 fake CPU devices (sharded_restore_driver
    trains the checkpoint fixture, restores at tp=2, and smokes a
    decode); assertions read its JSON row."""

    def test_restore_places_params_on_serving_mesh(
            self, sharded_subprocess):
        proc, row = sharded_subprocess('tests/sharded_restore_driver.py',
                                       timeout=600)
        assert proc.returncode == 0, (proc.stdout[-2000:],
                                      proc.stderr[-2000:])
        assert row is not None and row['ok'], row
        # Orbax placed every leaf exactly where the engine would.
        assert row['spec_mismatches'] == 0
        # And the tp-shardable leaves are genuinely split: per-device
        # bytes ≤ (1/tp + ε) of the global tree.
        assert row['sharded_leaves'] > 0
        assert row['per_device_frac'] <= row['max_frac']
        assert row['decoded_tokens'] == 3
