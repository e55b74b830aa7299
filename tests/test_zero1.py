"""ZeRO-1 cross-replica weight-update sharding (ISSUE-10 tentpole,
arxiv 2004.13336).

Two layers:

- in-process unit tests for the substrate (no SPMD compiles): the
  `zero_update_shardings` augmentation rule, the `train_mesh` helper,
  and the hlo_probe `partition_scatter_count` text heuristic;
- one subprocess run of tests/zero1_driver.py on 8 fake CPU devices
  (the sharded_subprocess fixture) covering parity, born-sharded init,
  compiled-HLO collective pins, checkpoint round-trips across dp
  extents, torn-state refusal, and the late-exporter gauges — the
  TestShardedComposition pattern: one run, many asserts.
"""
import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec


class TestZeroUpdateShardings:

    def _base(self, mesh, shape, *logical):
        from skypilot_tpu.parallel import sharding as sharding_lib
        return (jax.ShapeDtypeStruct(shape, jax.numpy.float32),
                NamedSharding(mesh, sharding_lib.spec_for(*logical)))

    def test_shards_first_divisible_dim_on_dp(self):
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        mesh = train_mesh(8)
        leaf, base = self._base(mesh, (2, 64, 4, 16), 'layers',
                                'embed', 'heads', None)
        out = zero_update_shardings(mesh, leaf, base)
        # dim0 (2) does not divide dp=8; dim1 (64, carrying fsdp at
        # extent 1) does — dp lands appended there. Trailing rank
        # padding is trimmed.
        assert out.spec == PartitionSpec('pp', ('fsdp', 'dp'), 'tp')

    def test_scalars_and_odd_shapes_stay_replicated(self):
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        mesh = train_mesh(8)
        scalar, s_sh = self._base(mesh, ())
        odd, o_sh = self._base(mesh, (3, 7))
        assert zero_update_shardings(mesh, scalar, s_sh) is s_sh
        assert zero_update_shardings(mesh, odd, o_sh) is o_sh

    def test_dp1_mesh_is_identity(self):
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        mesh = train_mesh(1)
        leaf, base = self._base(mesh, (64, 64), 'embed', None)
        assert zero_update_shardings(mesh, leaf, base) is base

    def test_already_dp_sharded_leaf_untouched(self):
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        mesh = train_mesh(8)
        leaf = jax.ShapeDtypeStruct((64, 64), jax.numpy.float32)
        base = NamedSharding(mesh, PartitionSpec('dp', None))
        assert zero_update_shardings(mesh, leaf, base) is base

    def test_lora_masked_opt_state_structure(self):
        """Under a LoRA multi_transform, flax's get_partition_spec
        collapses masked/empty optax nodes to prefix shardings — the
        augmentation must treat those as opaque (keep the base
        sharding) and still dp-shard the real adapter-moment leaves.
        Pure eval_shape, no compile."""
        import dataclasses

        from flax import linen as nn

        from skypilot_tpu.models import get_config
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        from skypilot_tpu.parallel import sharding as sharding_lib
        from skypilot_tpu.train import TrainConfig
        from skypilot_tpu.train.trainer import (TrainState, Transformer,
                                                make_optimizer)
        cfg = dataclasses.replace(get_config('test-tiny', lora_rank=8),
                                  param_dtype='float32')
        mesh = train_mesh(8)
        model = Transformer(cfg)
        tx = make_optimizer(TrainConfig(), lora_only=True)

        def init_fn(rng):
            variables = model.init(rng, jax.numpy.ones((1, 8),
                                                       jax.numpy.int32))
            return TrainState.create(apply_fn=model.apply,
                                     params=variables['params'], tx=tx)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        base = sharding_lib.tree_shardings(mesh, abstract)
        out = zero_update_shardings(mesh, nn.unbox(abstract).opt_state,
                                    nn.unbox(base).opt_state)
        flat = [s for s in jax.tree.leaves(out)
                if hasattr(s, 'spec')]
        assert flat
        dp_sharded = sum(
            1 for s in flat
            if any('dp' in ((e,) if isinstance(e, str)
                            else tuple(e or ()))
                   for e in s.spec))
        assert dp_sharded > 0  # the adapter moments picked up dp

    def test_tree_map_over_opt_state_like_tree(self):
        from skypilot_tpu.parallel import (train_mesh,
                                           zero_update_shardings)
        mesh = train_mesh(8)
        f32 = jax.numpy.float32
        abstract = {'count': jax.ShapeDtypeStruct((), f32),
                    'mu': {'w': jax.ShapeDtypeStruct((64, 256), f32)}}
        repl = NamedSharding(mesh, PartitionSpec())
        base = {'count': repl, 'mu': {'w': repl}}
        out = zero_update_shardings(mesh, abstract, base)
        assert out['count'].spec == PartitionSpec()
        assert out['mu']['w'].spec == PartitionSpec('dp')


class TestTrainMesh:

    def test_shape(self):
        from skypilot_tpu.parallel import train_mesh
        mesh = train_mesh(4)
        assert dict(mesh.shape)['dp'] == 4
        assert all(s == 1 for a, s in dict(mesh.shape).items()
                   if a != 'dp')

    def test_rejects_bad_dp(self):
        from skypilot_tpu.parallel import train_mesh
        with pytest.raises(ValueError):
            train_mesh(0)
        with pytest.raises(ValueError):
            train_mesh(len(jax.devices()) + 1)


class TestPartitionScatterProbe:

    # Operand references use the producing instruction's name, and the
    # partition-id producer is always named %partition-id[.N] in
    # optimized HLO — the probe keys on that.
    HLO = '''
  %partition-id.4 = u32[] partition-id()
  %ar = f32[512,64]{1,0} all-reduce(%g), replica_groups={}
  %scatter = f32[8,512]{1,0} fusion(f32[] %s, f32[512,64]{1,0} %ar, u32[] %partition-id.4), kind=kLoop
  %plain = f32[8,512]{1,0} fusion(f32[] %s, f32[512,64]{1,0} %ar), kind=kLoop
  %gatherish = s32[2,64,1,3]{3,2,1,0} fusion(s32[2,64]{1,0} %p, u32[] %partition-id.4), kind=kLoop
  %halver = f32[256,64]{1,0} fusion(f32[512,64]{1,0} %ar, u32[] %partition-id.4), kind=kLoop
'''

    def test_counts_partition_addressed_slices(self):
        from skypilot_tpu.parallel import hlo_probe
        # %scatter: 32768 -> 4096 elements (k=8) with a partition-id
        # operand. %plain lacks partition-id; %gatherish GROWS;
        # %halver is k=2.
        assert hlo_probe.partition_scatter_count(self.HLO) == 2
        assert hlo_probe.partition_scatter_count(self.HLO, shards=8) == 1
        assert hlo_probe.partition_scatter_count(self.HLO, shards=4) == 0

    def test_empty(self):
        from skypilot_tpu.parallel import hlo_probe
        assert hlo_probe.partition_scatter_count(
            '%r = f32[2] add(%a, %b)') == 0

    # The installed XLA names its operands and prints no shape beside
    # them; a tuple-valued definition (the all-reduce) has no one shape.
    HLO_NAMES_ONLY = '''
  %partition-id.4 = u32[] partition-id()
  %all-reduce.60 = (f32[512,64]{1,0}, f32[512,64]{1,0}) all-reduce(%a, %b), replica_groups={}
  %gte.1 = f32[512,64]{1,0} get-tuple-element(%all-reduce.60), index=1
  %norm = f32[] fusion(%w.1, %w.2), kind=kLoop
  %param.21 = f32[64,64]{1,0} parameter(20)
  %scatter = f32[64,64]{1,0} fusion(%norm, %gte.1, %partition-id.4), kind=kLoop
  %update = f32[64,64]{1,0} fusion(%param.21, %scatter, %norm, %partition-id.4), kind=kLoop
  %plain = f32[64,64]{1,0} fusion(%norm, %gte.1), kind=kLoop
  %halver = f32[256,64]{1,0} fusion(%gte.1, %partition-id.4), kind=kLoop
  ROOT %out = (f32[64,64]{1,0}) tuple(%update)
'''

    def test_operands_named_without_shapes_are_looked_up(self):
        from skypilot_tpu.parallel import hlo_probe
        # %scatter keeps 1/8 of %gte.1; %update's operands are all its
        # own size; %plain has no partition-id; %halver is k=2.
        assert hlo_probe.partition_scatter_count(
            self.HLO_NAMES_ONLY, shards=8) == 1
        assert hlo_probe.partition_scatter_count(self.HLO_NAMES_ONLY) == 2

    def test_tuple_valued_operand_is_not_a_shape(self):
        from skypilot_tpu.parallel import hlo_probe
        # An op that consumes the all-reduce's TUPLE beside partition-id
        # slices nothing: the tuple's first element is not its size.
        text = self.HLO_NAMES_ONLY + (
            '  %t = f32[64,64]{1,0} fusion(%all-reduce.60, '
            '%partition-id.4), kind=kLoop\n')
        assert hlo_probe.partition_scatter_count(text, shards=8) == 1


@pytest.mark.sharded
@pytest.mark.deadline(900)
class TestZero1Driver:
    """One subprocess run on 8 fake CPU devices; assertions read its
    JSON row (tests/zero1_driver.py documents the scenario)."""

    @pytest.fixture(scope='class')
    def row(self, sharded_subprocess):
        proc, row = sharded_subprocess('tests/zero1_driver.py',
                                       timeout=780)
        assert proc.returncode == 0, (proc.stdout[-2000:],
                                      proc.stderr[-2000:])
        assert row is not None, proc.stdout[-2000:]
        return row

    def test_driver_ok(self, row):
        assert row['ok'], row

    def test_loss_and_grad_norm_bit_parity(self, row):
        """Toggling optimizer sharding under the same dp mesh yields
        bit-identical loss AND grad_norm for 3 steps, with clipping
        ACTIVE — the accumulate-then-update path does not fork."""
        assert row['clip_active']
        assert row['parity_accum1']

    def test_parity_holds_under_grad_accum(self, row):
        assert row['parity_accum2']

    def test_moments_born_sharded(self, row):
        """Every optimizer-state leaf is placed exactly where
        zero_update_shardings says (jit init with out-shardings — the
        fp32 moments never materialize whole on one device), and dp
        genuinely splits them."""
        assert row['spec_mismatches'] == 0
        assert row['sharded_opt_leaves'] > 0

    def test_per_device_opt_bytes_bound(self, row):
        assert row['per_device_frac'] <= row['max_frac']

    def test_compiled_step_scatters_and_gathers(self, row):
        """The zero1 step's compiled HLO scatters gradients and
        all-gathers params; the plain step does neither. grad_accum
        composes: the scatter/gather counts do not multiply with the
        microbatch count."""
        assert row['zero_hlo']['reduce_scatter_effective'] > 0
        assert row['zero_hlo']['all_gather'] > 0
        assert row['base_hlo']['reduce_scatter_effective'] == 0
        assert row['base_hlo']['all_gather'] == 0
        assert row['zero_hlo_accum2']['reduce_scatter_effective'] == \
            row['zero_hlo']['reduce_scatter_effective']

    def test_checkpoint_roundtrip_same_dp(self, row):
        assert row['ckpt_same_dp_values']
        assert row['ckpt_same_dp_specs']

    def test_checkpoint_restores_across_dp_extents(self, row):
        assert row['ckpt_cross_dp_values']
        assert row['ckpt_cross_dp_frac'] <= 0.5 + 0.05

    def test_torn_checkpoint_never_loads_silently(self, row):
        assert row['corrupt_raises'], row.get('corrupt_error')
        assert row['partial_raises']

    def test_late_exporter_reads_gauges(self, row):
        assert row['gauges_ok']
