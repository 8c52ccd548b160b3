"""File-backed image pipeline: ImageFolder + Megatron samplers + the
imagenet example end-to-end on real files (reference
examples/imagenet/main_amp.py:188-218 ImageFolder/DataLoader path)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from apex_tpu.data import ImageFolderDataset, make_image_loader
from apex_tpu.transformer._data import (
    MegatronPretrainingRandomSampler,
    MegatronPretrainingSampler,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _example_env():
    """Subprocess env for the example runs: pinned to the CPU, with
    PYTHONPATH exactly the repo."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    return env


@pytest.fixture(scope="module")
def image_tree(tmp_path_factory):
    """12 PNGs in 3 class dirs (odd sizes to exercise crops)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("imgs")
    rng = np.random.RandomState(0)
    for ci, cls in enumerate(["ants", "bees", "cats"]):
        d = root / cls
        d.mkdir()
        for i in range(4):
            h, w = rng.randint(40, 90), rng.randint(40, 90)
            arr = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
            Image.fromarray(arr).save(d / f"img{i}.png")
    return str(root)


class TestImageFolderDataset:
    def test_scan_and_decode(self, image_tree):
        ds = ImageFolderDataset(image_tree, image_size=32, train=True)
        assert len(ds) == 12
        assert ds.class_to_idx == {"ants": 0, "bees": 1, "cats": 2}
        img, label = ds[0]
        assert img.shape == (32, 32, 3) and img.dtype == np.float32
        assert label == 0
        assert ds[11][1] == 2

    def test_eval_crop_deterministic(self, image_tree):
        ds = ImageFolderDataset(image_tree, image_size=32, train=False)
        a, _ = ds[3]
        b, _ = ds[3]
        np.testing.assert_array_equal(a, b)

    def test_normalization_applied(self, image_tree):
        ds = ImageFolderDataset(image_tree, image_size=32, train=False)
        img, _ = ds[0]
        # mean/std normalization moves values out of [0, 1]
        assert img.min() < -0.5


class TestLoaderOverSamplers:
    def test_epoch_covers_every_sample_once(self, image_tree):
        ds = ImageFolderDataset(image_tree, image_size=32, train=False)
        sampler = MegatronPretrainingSampler(
            total_samples=len(ds), consumed_samples=0,
            local_minibatch_size=4, data_parallel_rank=0,
            data_parallel_size=1)
        labels = []
        for x, y in make_image_loader(ds, sampler, num_workers=2):
            assert x.shape == (4, 32, 32, 3)
            labels.extend(y.tolist())
        assert sorted(labels) == sorted(
            lb for _, lb in ds.samples)

    def test_random_sampler_resumes(self, image_tree):
        ds = ImageFolderDataset(image_tree, image_size=32, train=False)

        def batches(consumed):
            s = MegatronPretrainingRandomSampler(
                total_samples=len(ds), consumed_samples=consumed,
                local_minibatch_size=4, data_parallel_rank=0,
                data_parallel_size=1)
            return [y.tolist()
                    for _, y in make_image_loader(ds, s, num_workers=2)]

        full = batches(0)
        resumed = batches(4)       # one batch already consumed
        assert full[1:] == resumed  # same epoch shuffle, continued


class TestExampleEndToEnd:
    @pytest.mark.slow   # e2e example; CI slow job
    def test_imagenet_example_trains_on_files(self, image_tree, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples",
                                          "imagenet_rn50.py"),
             "--data-dir", image_tree, "--batch", "4", "--steps", "2",
             "--image-size", "32", "--steps-per-epoch", "4",
             "--arch", "resnet18", "--num-classes", "3"],
            env=_example_env(), cwd=REPO, capture_output=True,
            text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "loss" in out.stdout and "prec@1" in out.stdout, out.stdout


class TestGptLmExample:
    @pytest.mark.slow   # e2e example; CI slow job
    def test_trains_on_text_and_samples(self, tmp_path):
        text = (
            "the quick brown fox jumps over the lazy dog. " * 200
        ).encode()
        f = tmp_path / "corpus.txt"
        f.write_bytes(text)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "gpt_lm.py"),
             "--data", str(f), "--steps", "80", "--batch", "8",
             "--seq", "64", "--layers", "2", "--hidden", "64",
             "--heads", "4", "--sample-tokens", "16", "--lr", "2e-3"],
            env=_example_env(), cwd=REPO, capture_output=True,
            text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        assert ("final loss" in out.stdout
                and "sample" in out.stdout), out.stdout
        # byte-level model on highly repetitive text must learn fast
        loss = float(out.stdout.split("final loss")[1].split()[0])
        assert loss < 3.0, out.stdout


class TestDevicePrefetch:
    def test_order_and_placement(self):
        import jax
        from apex_tpu.data import device_prefetch

        batches = [(np.full((2, 3), i, np.float32), np.array([i]))
                   for i in range(7)]
        out = list(device_prefetch(iter(batches), size=3))
        assert len(out) == 7
        for i, (im, lb) in enumerate(out):
            assert isinstance(im, jax.Array)   # actually on device
            assert float(np.asarray(im)[0, 0]) == i
            assert int(np.asarray(lb)[0]) == i

    def test_sharded_placement_over_mesh(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from apex_tpu.data import device_prefetch
        from apex_tpu.parallel.mesh import create_mesh

        mesh = create_mesh(dp=8)
        sh = NamedSharding(mesh, P("dp"))
        batches = [(np.arange(16, dtype=np.float32).reshape(16, 1),)
                   for _ in range(3)]
        out = list(device_prefetch(iter(batches), size=2, sharding=sh))
        assert len(out) == 3
        (im,) = out[0]
        assert im.sharding == sh
        assert len(im.addressable_shards) == 8
        np.testing.assert_array_equal(
            np.asarray(im), batches[0][0])

    def test_size_validation(self):
        from apex_tpu.data import device_prefetch

        with pytest.raises(ValueError):
            list(device_prefetch(iter([]), size=0))

    def test_abandoned_consumer_releases_producer(self):
        # An early break must unblock the producer thread instead of
        # leaving it parked on q.put for the process lifetime (ADVICE r4).
        import threading

        from apex_tpu.data import device_prefetch

        produced = []

        def source():
            i = 0
            while True:
                produced.append(i)
                yield (np.full((2,), i, np.float32),)
                i += 1

        before = set(threading.enumerate())
        it = device_prefetch(source(), size=2)
        next(it)
        workers = [t for t in threading.enumerate() if t not in before]
        assert len(workers) == 1, workers
        it.close()  # GeneratorExit → finally → stop event + drain
        workers[0].join(timeout=10)
        assert not workers[0].is_alive(), "producer still running after close"
        assert len(produced) <= 6  # bounded: ~size+in-flight, not unbounded
