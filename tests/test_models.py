"""Model DSL + zoo tests.

Mirrors reference LayerSpec.scala (DSL builds a loadable LeNet; AlexNet
prototxt loads into a solver) and extends it: the programmatic zoo builders
must agree with the stock reference prototxts on parameter shapes/counts
and blob geometry.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sparknet_tpu import proto
from sparknet_tpu.graph import CompiledNet, TRAIN, TEST
from sparknet_tpu.models import (dsl, zoo, lenet, cifar10_full, caffenet,
                                 googlenet)
from tests.lm_family import stack_contract

REF = "/root/reference/caffe"


def param_shapes_of(net):
    return {k: v[0] for k, v in
            {k: (tuple(s),) for k, (s, f, lr, dc) in
             sorted(net.param_meta.items())}.items()}


class TestDSL:
    def test_rdd_layer_matches_scala_shape(self):
        lp = dsl.RDDLayer("data", [100, 3, 32, 32], include=dsl.TRAIN)
        assert lp.type == "JavaData"
        assert list(lp.java_data_param.shape.dim) == [100, 3, 32, 32]
        assert lp.include[0].enum_name("phase") == "TRAIN"
        assert list(lp.top) == ["data"]

    def test_lenet_via_dsl_builds_and_trains(self):
        net = CompiledNet(lenet(batch_size=8), TRAIN)
        params, state = net.init(jax.random.PRNGKey(0))
        assert params["conv1"][0].shape == (20, 1, 5, 5)
        assert params["ip1"][0].shape == (500, 800)
        batch = {"data": jnp.asarray(
            np.random.RandomState(0).rand(8, 1, 28, 28), jnp.float32),
            "label": jnp.arange(8) % 10}
        loss, _ = net.loss_fn(params, state, batch,
                              rng=jax.random.PRNGKey(1))
        assert abs(float(loss) - np.log(10)) < 0.3

    def test_lenet_matches_reference_prototxt_shapes(self):
        ref = proto.load_prototxt(f"{REF}/examples/mnist/lenet_train_test.prototxt",
                                  "NetParameter")
        refnet = CompiledNet(ref, TRAIN,
                             feed_shapes={"data": (64, 1, 28, 28),
                                          "label": (64,)})
        ours = CompiledNet(lenet(batch_size=64), TRAIN)
        for key in refnet.param_meta:
            assert refnet.param_meta[key][0] == ours.param_meta[key][0], key

    def test_prototxt_emission_roundtrip(self):
        net = lenet(batch_size=4)
        text = proto.format_prototxt(net)
        again = proto.parse_prototxt(text, "NetParameter")
        assert again == net
        CompiledNet(again, TRAIN)  # still compiles


class TestZooParity:
    def test_cifar10_full_matches_reference(self):
        ref = proto.load_prototxt(
            f"{REF}/examples/cifar10/cifar10_full_train_test.prototxt",
            "NetParameter")
        refnet = CompiledNet(ref, TRAIN, feed_shapes={"data": (100, 3, 32, 32),
                                                      "label": (100,)})
        ours = CompiledNet(cifar10_full(batch_size=100), TRAIN)
        assert set(refnet.param_meta) == set(ours.param_meta)
        for key in refnet.param_meta:
            rs, rf, rlr, rdc = refnet.param_meta[key]
            os_, of, olr, odc = ours.param_meta[key]
            assert rs == os_, key
            assert (rlr, rdc) == (olr, odc), key
        # blob geometry identical
        for blob, shape in refnet.blob_shapes.items():
            assert ours.blob_shapes[blob] == shape, blob

    def test_caffenet_matches_reference(self):
        ref = proto.load_prototxt(
            f"{REF}/models/bvlc_reference_caffenet/train_val.prototxt",
            "NetParameter")
        refnet = CompiledNet(ref, TRAIN,
                             feed_shapes={"data": (8, 3, 227, 227),
                                          "label": (8,)})
        ours = CompiledNet(caffenet(batch_size=8), TRAIN)
        assert set(refnet.param_meta) == set(ours.param_meta)
        for key in refnet.param_meta:
            assert refnet.param_meta[key][0] == ours.param_meta[key][0], key
        ref_total = sum(int(np.prod(s)) for s, *_ in refnet.param_meta.values())
        our_total = sum(int(np.prod(s)) for s, *_ in ours.param_meta.values())
        assert ref_total == our_total == 60965224

    def test_googlenet_matches_reference_param_count(self):
        ref = proto.load_prototxt(
            f"{REF}/models/bvlc_googlenet/train_val.prototxt", "NetParameter")
        refnet = CompiledNet(ref, TRAIN,
                             feed_shapes={"data": (2, 3, 224, 224),
                                          "label": (2,)})
        ours = CompiledNet(googlenet(batch_size=2), TRAIN)
        ref_shapes = {k: v[0] for k, v in refnet.param_meta.items()}
        our_shapes = {k: v[0] for k, v in ours.param_meta.items()}
        assert ref_shapes == our_shapes
        assert sorted(ours.output_blobs) == sorted(refnet.output_blobs)

    def test_googlenet_forward(self):
        net = CompiledNet(googlenet(batch_size=2, with_aux=False), TRAIN)
        params, state = net.init(jax.random.PRNGKey(0))
        batch = {"data": jnp.asarray(
            np.random.RandomState(0).randn(2, 3, 224, 224) * 0.1,
            jnp.float32), "label": jnp.asarray([1, 2])}
        loss, (blobs, _) = net.loss_fn(params, state, batch,
                                       rng=jax.random.PRNGKey(1))
        assert np.isfinite(float(loss))
        assert blobs["pool5/7x7_s1"].shape == (2, 1024, 1, 1)


@pytest.mark.parametrize("builder,args", [
    ("transformer_lm", dict(moe_experts=4)),
    ("transformer_lm", dict(with_data=False)),
    ("qwen3_next", {}), ("smallthinker", {}), ("lfm2_moe", {}),
    ("keye_vl2", dict(index_stats=True, moe_stats=True)),
    ("nemotron_h", dict(layers=(35, 44), ssm_stats=True)),
    ("glm4_moe_lite", {}), ("laguna", dict(moe_stats=True))])
def test_an_lm_builder_keeps_the_stacks_naming_contract(builder, args):
    """`zoo._lm_stack` states it and `tests/lm_family.py:stack_contract`
    reads it off the built net, at the published sizes: a block's layers
    together under "block{i}/", one blob in from the block before, one
    out; what the remat groups and the scan runs of graph/compiler.py
    stand on."""
    net = getattr(zoo, builder)(**args)
    assert len(stack_contract(net)) == len(
        {lp.name.split("/")[0] for lp in net.layer if "/" in lp.name})


def test_the_pipelines_block_is_transformer_lms_without_its_prefix():
    _, block, _ = zoo.transformer_lm_pieces()
    whole = [lp.name for lp in zoo.transformer_lm().layer
             if lp.name.startswith("block0/")]
    assert [lp.name for lp in block.layer][1:] == \
        [name[len("block0/"):] for name in whole]
