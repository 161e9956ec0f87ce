"""Kill-and-resume recovery test (SURVEY §5 failure detection / VERDICT r3
missing #6).

Recovery model (documented in docs/faq/failure_recovery.md): a hard worker
failure is survived by restarting the job from the last per-epoch
checkpoint — the same story as the reference (whose PS tracker restarts
jobs; there is no in-job elastic rejoin there either, scheduler docs
aside). This test proves the mechanism end to end: a real training process
SIGKILLs itself mid-job after writing its epoch-2 checkpoint, and a second
process resumes from that checkpoint with --load-epoch and finishes to
high accuracy without retraining epochs 1-2.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.chaos

WORKER = os.path.join(os.path.dirname(__file__), "resume_worker.py")


def _run(args, fault=None):
    # force the CPU platform in the child and drop the parent's virtual
    # device count (same strip as tests/test_dist.py)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "MXTPU_FAULT_INJECT")}
    env["JAX_PLATFORMS"] = "cpu"
    if fault is not None:
        env["MXTPU_FAULT_INJECT"] = fault
    return subprocess.run(
        [sys.executable, WORKER] + args,
        capture_output=True, text=True, env=env, timeout=600)


def test_kill_and_resume(tmp_path):
    prefix = str(tmp_path / "job")

    # run 1: hard-killed (SIGKILL -> rc=-9) after the epoch-2 checkpoint
    r1 = _run([prefix, "4", "--crash-at", "2"])
    assert r1.returncode != 0, "crash run should not exit cleanly"
    assert "simulating hard failure" in r1.stdout
    assert not os.path.exists(prefix + ".acc"), \
        "killed run must not have completed"
    assert os.path.exists(prefix + "-0002.params"), \
        "epoch-2 checkpoint must survive the kill"

    # run 2: resume from the surviving checkpoint and finish
    r2 = _run([prefix, "4", "--load-epoch", "2"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "Resume training from epoch 2" in r2.stdout
    with open(prefix + ".acc") as f:
        acc = float(f.read())
    assert acc > 0.9, acc
    # resumed run trained only epochs 3..4: exactly two new checkpoints
    assert os.path.exists(prefix + "-0004.params")


def test_sigkill_during_checkpoint_write_auto_resume(tmp_path):
    """The tentpole acceptance case: the process is SIGKILLed at byte 800
    of the THIRD checkpoint's params write (faultinject ``ckpt_write``,
    armed via env in the child). The torn checkpoint has no manifest, so
    auto-resume falls back to the epoch-2 checkpoint and finishes to the
    same accuracy bar as the legacy kill-and-resume test — proving a
    crash at ANY byte of a save loses at most the epochs since the last
    good checkpoint, never the job."""
    prefix = str(tmp_path / "job")
    ckdir = str(tmp_path / "ck")

    r1 = _run([prefix, "4", "--manager-dir", ckdir],
              fault="ckpt_write:byte=800:action=kill"
                    ":match=params.params:call=3")
    assert r1.returncode != 0, "killed run must not exit cleanly"
    assert "faultinject: SIGKILL at site 'ckpt_write'" in r1.stdout
    assert not os.path.exists(prefix + ".acc")
    # epoch-1/2 checkpoints committed; the epoch-3 one is torn (partial
    # params.params, manifest never written)
    assert os.path.exists(os.path.join(ckdir, "ckpt-000002",
                                       "MANIFEST.json"))
    assert not os.path.exists(os.path.join(ckdir, "ckpt-000003",
                                           "MANIFEST.json"))

    r2 = _run([prefix, "4", "--manager-dir", ckdir, "--auto-resume"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "continuing at epoch 2" in r2.stdout, r2.stdout[-3000:]
    with open(prefix + ".acc") as f:
        acc = float(f.read())
    assert acc > 0.9, acc


def test_corrupted_checkpoint_falls_back_on_resume(tmp_path):
    """Bit-rot below the filesystem: the newest checkpoint's params file
    is overwritten in place (size preserved, CRC broken). auto-resume
    must detect it via the manifest, fall back one epoch, and finish."""
    prefix = str(tmp_path / "job")
    ckdir = str(tmp_path / "ck")

    r1 = _run([prefix, "3", "--manager-dir", ckdir])
    assert r1.returncode == 0, r1.stdout + r1.stderr

    params = os.path.join(ckdir, "ckpt-000003", "params.params")
    size = os.path.getsize(params)
    blob = bytearray(open(params, "rb").read())
    blob[size // 4: size // 2] = os.urandom(size // 2 - size // 4)
    with open(params, "wb") as f:
        f.write(bytes(blob))

    os.unlink(prefix + ".acc")
    r2 = _run([prefix, "4", "--manager-dir", ckdir, "--auto-resume"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "failed validation" in r2.stdout, r2.stdout[-3000:]
    assert "continuing at epoch 2" in r2.stdout, r2.stdout[-3000:]
    with open(prefix + ".acc") as f:
        acc = float(f.read())
    assert acc > 0.9, acc
