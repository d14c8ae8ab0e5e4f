"""Examples smoke tests: every shipped example must run end-to-end on the
CPU mesh (the reference's examples are exercised by its L1 drivers,
tests/L1/common/run_test.sh; here they run directly, tiny configs).

Marked ``slow`` but left IN the default run on purpose: the smokes
cost ~90 s total and the examples have rotted silently before (the
flat-master refactor). Deselect with ``-m 'not slow'`` for a quick
iteration loop; the per-test timeout bounds the worst case at 5 min."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=300):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


@pytest.mark.slow
def test_imagenet_example_dp8():
    out = _run(["examples/imagenet/main_amp.py", "--arch", "resnet18",
                "--steps-per-epoch", "4", "--batch-size", "8",
                "--image-size", "32", "--data-parallel", "8",
                "--print-freq", "2"])
    assert "img/s" in out


@pytest.mark.slow
def test_imagenet_example_real_data(tmp_path):
    """--data: train + validate end-to-end from a generated on-disk
    image-folder through the sharded loader -> native decode/crop/flip
    -> background device prefetch, with input-wait telemetry."""
    import json
    from apex_tpu.data import write_image_folder
    root = str(tmp_path / "ds")
    write_image_folder(root, classes=4, per_class=12, size=(40, 40),
                       seed=1)
    telem = str(tmp_path / "TELEM_data.jsonl")
    out = _run(["examples/imagenet/main_amp.py", "--arch", "tiny",
                "--image-size", "32", "--batch-size", "8",
                "--data", root, "--steps-per-epoch", "0",
                "--print-freq", "2", "--telemetry", telem])
    assert "4 classes" in out
    assert "in_wait" in out          # input-wait accounting printed
    assert "Prec@1" in out           # validation ran on real batches
    # the sidecar carries input_wait_ms on its step records
    recs = [json.loads(l) for l in open(telem) if l.strip()]
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps and all("input_wait_ms" in r for r in steps)


@pytest.mark.slow
def test_imagenet_example_vit():
    out = _run(["examples/imagenet/main_amp.py", "--arch", "vit_tiny",
                "--steps-per-epoch", "4", "--batch-size", "8",
                "--image-size", "32", "--print-freq", "2"])
    assert "img/s" in out
    assert "Prec@1" in out


@pytest.mark.slow
def test_seq2seq_example():
    out = _run(["examples/seq2seq/train_translation.py", "--steps", "12",
                "--batch-size", "8", "--seq-len", "10", "--embed-dim",
                "48", "--print-freq", "6", "--decode-samples", "2"])
    assert "loss" in out
    assert "greedy exact-match" in out


@pytest.mark.slow
def test_lm_ring_example():
    out = _run(["examples/lm/train_ring.py", "--steps", "2",
                "--seq-len", "256", "--batch-size", "2",
                "--vocab", "128"])
    assert "tok/s" in out


def test_lm_ring_example_fused_head_grad_accum():
    # the flagship long-context combo: chunked fused-head loss
    # (custom_vjp) inside the grad-accumulation scan inside shard_map,
    # with dynamic scaling
    out = _run(["examples/lm/train_ring.py", "--steps", "2",
                "--seq-len", "256", "--batch-size", "2",
                "--vocab", "128", "--head-chunk", "32",
                "--grad-accum", "2", "--loss-scale", "dynamic"])
    assert "tok/s" in out


@pytest.mark.slow
def test_dcgan_example():
    out = _run(["examples/dcgan/main_amp.py", "--steps", "2"])
    assert "done" in out


@pytest.mark.slow
def test_simple_ddp_example():
    out = _run(["examples/simple/distributed/"
                "distributed_data_parallel.py"])
    assert "final loss" in out


@pytest.mark.slow
def test_zero_example():
    out = _run(["examples/simple/distributed/zero_sharded_optimizer.py"])
    assert "final loss" in out
    # loss decreased over the run
    import re
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert losses[-1] < losses[0]
