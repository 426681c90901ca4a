"""On-device DataTransformer == native host kernel, bit for bit.

The device path (data/device_transform.py) must reproduce the reference
data_transformer.cpp:42-51 semantics the native host kernel
(native/pipeline.cpp transform_batch) already implements: full-size mean
subtracted at the source crop-window index BEFORE the mirror, per-channel
mean after, then scale. Both paths share float32 op order, so the
comparison below is exact (atol=0), not approximate.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sparknet_tpu import native
from sparknet_tpu.data.transforms import DataTransformer
from sparknet_tpu.data.device_transform import (DeviceTransformer,
                                                build_device_transformer,
                                                aux_keys)
from sparknet_tpu.proto import Message


def _batch(n=6, c=3, h=40, w=40, seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, (n, c, h, w)).astype(np.uint8)


def _run_device(devt, images, aux, precropped=False):
    fn = jax.jit(devt.device_fn(precropped=precropped))
    out = fn({"data": jnp.asarray(images), "label": jnp.zeros(len(images)),
              **{k: jnp.asarray(v) for k, v in aux.items()}})
    assert set(out) == {"data", "label"}          # aux consumed
    return np.asarray(out["data"])


def test_crop_mirror_full_mean_scale_exact():
    images = _batch()
    n, c, h, w = images.shape
    crop = 28
    mean = np.random.RandomState(1).rand(c, h, w).astype(np.float32) * 120
    rs = np.random.RandomState(2)
    ys = rs.randint(0, h - crop + 1, n).astype(np.int32)
    xs = rs.randint(0, w - crop + 1, n).astype(np.int32)
    flips = rs.randint(0, 2, n).astype(np.uint8)

    host = native.transform_batch(images, crop, ys=ys, xs=xs, mirror=flips,
                                  mean=mean, scale=0.00390625,
                                  full_mean=True)

    tp = Message("TransformationParameter", crop_size=crop, mirror=True,
                 scale=0.00390625)
    devt = build_device_transformer(tp, phase=0)
    devt.h.mean, devt.h.full_mean = mean, True    # bypass mean_file I/O
    ky, kx, kf = aux_keys("data")
    dev = _run_device(devt, images, {ky: ys, kx: xs, kf: flips})
    np.testing.assert_array_equal(dev, host)


def test_no_crop_full_mean_exact_cifar_shape():
    # the cifar10_full configuration: mean_file only, no crop, no mirror
    images = _batch(8, 3, 32, 32, seed=3)
    mean = np.random.RandomState(4).rand(3, 32, 32).astype(np.float32) * 100
    tp = Message("TransformationParameter")
    host_t = DataTransformer(tp, phase=0, rng=np.random.RandomState(0))
    host_t.mean, host_t.full_mean = mean, True
    host = host_t(images)

    devt = DeviceTransformer(
        DataTransformer(tp, phase=0, rng=np.random.RandomState(0)))
    devt.h.mean, devt.h.full_mean = mean, True
    dev = _run_device(devt, images, {})
    np.testing.assert_array_equal(dev, host)


def test_per_channel_mean_and_center_crop_test_phase():
    images = _batch(5, 3, 36, 36, seed=5)
    crop = 24
    tp = Message("TransformationParameter", crop_size=crop, scale=2.0)
    tp.mean_value.extend([10.0, 20.0, 30.0])
    seed = 7
    host_t = DataTransformer(tp, phase=1, rng=np.random.RandomState(seed))
    host = host_t(images)

    devt = build_device_transformer(tp, phase=1,
                                    rng=np.random.RandomState(seed))
    aux = devt.aux(len(images), images.shape[1:])
    dev = _run_device(devt, images, aux)
    np.testing.assert_array_equal(dev, host)


def test_shared_rng_matches_host_stream_train_phase():
    # same seed => host mode and device mode draw identical augmentations
    images = _batch(10, 3, 32, 32, seed=8)
    crop = 28
    tp = Message("TransformationParameter", crop_size=crop, mirror=True)
    host_t = DataTransformer(tp, phase=0, rng=np.random.RandomState(11))
    host = host_t(images)

    devt = build_device_transformer(tp, phase=0,
                                    rng=np.random.RandomState(11))
    aux = devt.aux(len(images), images.shape[1:])
    dev = _run_device(devt, images, aux)
    np.testing.assert_array_equal(dev, host)


def test_raw_overrides_shapes():
    tp = Message("TransformationParameter", crop_size=20, mirror=True)
    devt = build_device_transformer(tp, phase=0)
    over = devt.raw_overrides(16, (3, 32, 32))
    ky, kx, kf = aux_keys("data")
    assert over == {"data": (16, 3, 32, 32), ky: (16,), kx: (16,),
                    kf: (16,)}


def test_solver_device_transform_end_to_end(tmp_path):
    """A Solver fed raw uint8 + aux under set_input_transform reaches the
    same loss as one fed the host-transformed float batch (same params,
    same rng key) — the transform really runs inside the jitted step."""
    from sparknet_tpu.models import zoo
    from sparknet_tpu.solver.solver import Solver

    tp = Message("TransformationParameter", crop_size=24, mirror=True)
    images = _batch(16, 3, 32, 32, seed=13)
    labels = np.random.RandomState(14).randint(0, 10, 16)

    seed = 21
    host_t = DataTransformer(tp, phase=0, rng=np.random.RandomState(seed))
    host_batch = {"data": host_t(images), "label": labels}

    devt = build_device_transformer(tp, phase=0,
                                    rng=np.random.RandomState(seed))
    aux = devt.aux(16, (3, 32, 32))
    raw_batch = {"data": images, "label": labels, **aux}

    def mk():
        sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                     display=0, random_seed=5)
        return Solver(sp, net_param=zoo.cifar10_full(batch_size=16),
                      feed_shapes={"data": (16, 3, 24, 24), "label": (16,)})

    s_host = mk()
    l_host = float(s_host.train_step(host_batch))

    s_dev = mk()
    s_dev.set_input_transform(devt.device_fn(),
                              devt.raw_overrides(16, (3, 32, 32)))
    l_dev = float(s_dev.train_step(raw_batch))
    assert l_host == pytest.approx(l_dev, rel=1e-6)
    # and the updated params agree
    for k in s_host.params:
        for a, b in zip(jax.tree_util.tree_leaves(s_host.params[k]),
                        jax.tree_util.tree_leaves(s_dev.params[k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


def _make_lmdb(path, n=60, c=3, h=32, w=32, seed=0):
    from sparknet_tpu.data.lmdb import LMDBWriter
    from sparknet_tpu.data.datum import array_to_datum
    rs = np.random.RandomState(seed)
    imgs = rs.randint(0, 256, (n, c, h, w)).astype(np.uint8)
    labels = rs.randint(0, 10, n)
    with LMDBWriter(path) as wtr:
        for i in range(n):
            wtr.put(b"%08d" % i, array_to_datum(imgs[i], int(labels[i])))
    return imgs, labels


def test_device_cache_matches_streaming(tmp_path):
    """Device-cached source (HBM-resident records + ctl-array steps) yields
    the same transformed batches as the streaming device mode — same
    sequential cursor, same host rng draws."""
    from sparknet_tpu.data.db_source import DatumBatchSource
    from sparknet_tpu.data.device_cache import (DeviceCachedSource,
                                                maybe_device_cache)
    imgs, labels = _make_lmdb(str(tmp_path / "db"))
    tp = Message("TransformationParameter", crop_size=28, mirror=True)

    def mk(seed):
        return DatumBatchSource(str(tmp_path / "db"), 16,
                                transform_param=tp, seed=seed,
                                device_transform=True)

    stream = mk(7)
    sfn = jax.jit(stream.device_fn)
    cached = maybe_device_cache(mk(7))
    assert isinstance(cached, DeviceCachedSource)
    cfn = jax.jit(cached.device_fn)
    si, ci = iter(stream), iter(cached)
    for _ in range(5):      # crosses the 60-record wrap at batch 4
        sb = {k: jnp.asarray(v) for k, v in next(si).items()}
        sout = sfn(sb)
        cb = {k: jnp.asarray(v) for k, v in next(ci).items()}
        cout = cfn(cb)
        np.testing.assert_array_equal(np.asarray(cout["data"]),
                                      np.asarray(sout["data"]))
        np.testing.assert_array_equal(np.asarray(cout["label"]),
                                      np.asarray(sout["label"]))
    assert cached.raw_feed_overrides["data"] is None
    assert cached.raw_feed_overrides["label"] is None
    assert cached.raw_feed_overrides["data#ctl"] == (16, 4)


def test_device_cache_budget_gate(tmp_path):
    from sparknet_tpu.data.db_source import DatumBatchSource
    from sparknet_tpu.data.device_cache import maybe_device_cache
    _make_lmdb(str(tmp_path / "db"))
    src = DatumBatchSource(str(tmp_path / "db"), 16, device_transform=True)
    assert maybe_device_cache(src, budget_mb=1e-6) is src   # too big
    host = DatumBatchSource(str(tmp_path / "db"), 16)
    assert maybe_device_cache(host) is host                 # host mode


def test_check_batch_raw_overrides_errors():
    from sparknet_tpu.models import zoo
    from sparknet_tpu.solver.solver import Solver
    tp = Message("TransformationParameter", crop_size=24)
    devt = build_device_transformer(tp, phase=0)
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0)
    s = Solver(sp, net_param=zoo.cifar10_full(batch_size=4),
               feed_shapes={"data": (4, 3, 24, 24), "label": (4,)})
    s.set_input_transform(devt.device_fn(),
                          devt.raw_overrides(4, (3, 32, 32)))
    ky, kx, _ = aux_keys("data")
    good = {"data": np.zeros((4, 3, 32, 32), np.uint8),
            "label": np.zeros(4, np.int32),
            ky: np.zeros(4, np.int32), kx: np.zeros(4, np.int32)}
    s.check_batch(good)                            # raw extent accepted
    bad = dict(good, data=np.zeros((4, 3, 24, 24), np.float32))
    with pytest.raises(ValueError, match="data"):
        s.check_batch(bad)                         # cropped shape rejected


def test_device_cache_chunked_upload_matches(tmp_path, monkeypatch):
    """SPARKNET_CACHE_CHUNK_MB: a tiny chunk size forces the multi-part
    upload + on-device concatenate path; resident contents must be
    identical to the single-put path."""
    from sparknet_tpu.data.db_source import DatumBatchSource
    from sparknet_tpu.data.device_cache import DeviceCachedSource
    imgs, labels = _make_lmdb(str(tmp_path / "db"))

    def mk():
        return DatumBatchSource(str(tmp_path / "db"), 16, seed=3,
                                device_transform=True)

    monkeypatch.setenv("SPARKNET_CACHE_CHUNK_MB", "0.002")  # ~1 record
    chunked = DeviceCachedSource(mk())
    monkeypatch.setenv("SPARKNET_CACHE_CHUNK_MB", "1024")
    single = DeviceCachedSource(mk())
    np.testing.assert_array_equal(np.asarray(chunked._images),
                                  np.asarray(single._images))
    np.testing.assert_array_equal(np.asarray(chunked._labels),
                                  np.asarray(single._labels))


def test_device_cache_gates(tmp_path):
    """The cache is a single-process, iter_size==1 optimization: iter_size
    > 1 would stack resident arrays on the host per micro-batch, and
    multi-process check_batch slicing doesn't apply to whole-dataset
    resident arrays — both must fall back to the streaming source."""
    from sparknet_tpu.data.db_source import DatumBatchSource
    from sparknet_tpu.data.device_cache import maybe_device_cache
    _make_lmdb(str(tmp_path / "db"))
    src = DatumBatchSource(str(tmp_path / "db"), 16, device_transform=True)
    assert maybe_device_cache(src, iter_size=4) is src
    assert maybe_device_cache(src, iter_size=1) is not src


# -- the selector form: every configuration, bit for bit -------------------

_KY, _KX, _KF = aux_keys("data")


def _edge_case_batch(n, c, h, w, crop, mirror, seed):
    """Records with the values 0 and 255, windows at both edges of the
    record, flips mixed: what a one-hot selector could get wrong."""
    rs = np.random.RandomState(seed)
    images = rs.randint(0, 256, (n, c, h, w)).astype(np.uint8)
    images[0], images[1 % n] = 0, 255
    aux = {}
    if crop:
        ys = rs.randint(0, h - crop + 1, n)
        xs = rs.randint(0, w - crop + 1, n)
        ys[:4] = [0, h - crop, 0, h - crop][:n]
        xs[:4] = [w - crop, 0, 0, w - crop][:n]
        aux[_KY], aux[_KX] = ys.astype(np.int32), xs.astype(np.int32)
    if mirror:
        aux[_KF] = (np.arange(n) % 2 == 0).astype(np.uint8)
    return images, aux


def _transformer(crop, mean_kind, mirror, scale, record_shape):
    tp = Message("TransformationParameter", mirror=bool(mirror), scale=scale)
    if crop:
        tp.crop_size = crop
    if mean_kind == "mean_value":
        tp.mean_value.extend([104.25, 116.7, 122.9])
    devt = build_device_transformer(tp, phase=0)
    if mean_kind == "mean_file":                  # bypass mean_file I/O
        devt.h.mean = (np.random.RandomState(1).rand(*record_shape)
                       .astype(np.float32) * 255)
        devt.h.full_mean = True
    return devt


def _host(devt, images, aux):
    """The host kernel on the same draws (no crop: the whole square
    record is the window)."""
    t = devt.h
    return native.transform_batch(
        images, t.crop_size or images.shape[2], ys=aux.get(_KY),
        xs=aux.get(_KX), mirror=aux.get(_KF), mean=t.mean, scale=t.scale,
        full_mean=t.full_mean)


def _device(devt, images, aux, precropped=False):
    if precropped and devt.h.crop_size:           # what data/wire.py ships
        crop = devt.h.crop_size
        images = np.stack([im[:, y:y + crop, x:x + crop] for im, y, x in
                           zip(images, aux[_KY], aux[_KX])])
    out = _run_device(devt, images, aux, precropped)
    assert out.dtype == np.float32
    return out


@pytest.mark.parametrize("precropped", [False, True])
@pytest.mark.parametrize("scale", [1.0, 0.017])
@pytest.mark.parametrize("mirror", [False, True])
@pytest.mark.parametrize("mean_kind", ["mean_file", "mean_value", "no_mean"])
@pytest.mark.parametrize("crop", [0, 28])
def test_bit_equal_to_host_kernel(crop, mean_kind, mirror, scale,
                                  precropped):
    # cropped records are not square, so a transposed selector shows
    c, h, w = (3, 40, 36) if crop else (3, 32, 32)
    images, aux = _edge_case_batch(6, c, h, w, crop, mirror, seed=17)
    devt = _transformer(crop, mean_kind, mirror, scale, (c, h, w))
    np.testing.assert_array_equal(_device(devt, images, aux, precropped),
                                  _host(devt, images, aux))


@pytest.mark.parametrize("batch_kind", ["all_flip", "no_flip", "batch_of_1"])
@pytest.mark.parametrize("mean_kind", ["mean_file", "mean_value"])
@pytest.mark.parametrize("crop", [0, 28])
def test_bit_equal_uniform_flips_and_batch_of_one(crop, mean_kind,
                                                  batch_kind):
    c, h, w = (3, 40, 36) if crop else (3, 32, 32)
    n = 1 if batch_kind == "batch_of_1" else 5
    images, aux = _edge_case_batch(n, c, h, w, crop, True, seed=23)
    aux[_KF] = np.full(n, batch_kind != "no_flip", np.uint8)
    devt = _transformer(crop, mean_kind, True, 0.5, (c, h, w))
    np.testing.assert_array_equal(_device(devt, images, aux),
                                  _host(devt, images, aux))


@pytest.mark.parametrize("crop", [0, 28])
def test_float_records_select_exactly(crop):
    """float_data records (db_source ships them as float32) hold values no
    bfloat16 does: they take the float32 selectors."""
    c, h, w = 3, 40, 36
    images, aux = _edge_case_batch(6, c, h, w, crop, True, seed=29)
    images = (np.random.RandomState(30).randn(*images.shape) * 100) \
        .astype(np.float32)
    devt = _transformer(crop, "mean_file", True, 0.25, (c, h, w))
    devt.h.rng = np.random.RandomState(31)
    aux = devt.aux(len(images), (c, h, w))
    devt.h.rng = np.random.RandomState(31)  # the host call draws the same
    np.testing.assert_array_equal(_device(devt, images, aux),
                                  devt.h(images))


def _whole_and_feed(n=8):
    c, h, w, crop = 3, 40, 36, 28
    images, aux = _edge_case_batch(n, c, h, w, crop, True, seed=37)
    devt = _transformer(crop, "mean_file", True, 0.017, (c, h, w))
    feed = {"data": jnp.asarray(images),
            **{k: jnp.asarray(v) for k, v in aux.items()}}
    return devt.device_fn(), feed, _host(devt, images, aux)


def test_scan_over_micro_batches_equals_whole_batch():
    fn, feed, host = _whole_and_feed()
    micro = jax.tree_util.tree_map(
        lambda a: a.reshape((4, 2) + a.shape[1:]), feed)
    _, out = jax.jit(lambda m: jax.lax.scan(
        lambda carry, b: (carry, fn(b)["data"]), 0, m))(micro)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(host.shape), host)


def test_shard_map_slices_equal_whole_batch():
    from jax.sharding import Mesh, PartitionSpec as P
    from sparknet_tpu.parallel.compat import shard_map
    fn, feed, host = _whole_and_feed()
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    out = jax.jit(shard_map(lambda b: fn(b)["data"], mesh=mesh,
                            in_specs=(P("data"),), out_specs=P("data")))(
        feed)
    np.testing.assert_array_equal(np.asarray(out), host)
