import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_py(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run python code in a subprocess with N simulated CPU devices.
    Multi-device tests must run out-of-process because jax locks the device
    count at first init. The child is pinned to the CPU backend: on a host
    with a chip, the parent may already hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


_GUARDED_MODULES = ("test_trainer", "test_serve", "test_scheduler",
                    "test_obs")


@pytest.fixture(autouse=True)
def _no_hidden_host_transfers(request):
    """Transfer guard over the trainer/serving test modules (DESIGN.md
    §12): library code under src/repro must not pull device buffers to
    host implicitly (np.asarray / float / .item on a jax Array) — the
    sanctioned sync is an explicit jax.device_get. Test-file code may
    pull freely (asserting on values is what tests do); only events
    originating inside src/repro fail."""
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _GUARDED_MODULES:
        yield
        return
    from repro.analysis.hostsync import guard_host_transfers
    with guard_host_transfers(mode="record") as events:
        yield
    bad = [ev for ev in events
           if not ev.sanctioned and not ev.internal
           and os.path.join("src", "repro") in ev.origin]
    if bad:
        lines = "\n".join(f"  {ev.method} at {ev.origin}"
                          for ev in {(e.method, e.origin): e
                                     for e in bad}.values())
        pytest.fail(
            f"implicit device->host transfer(s) in library code "
            f"(use jax.device_get):\n{lines}", pytrace=False)


def make_batch(cfg, key, B=2, L=33):
    batch = {"tokens": jax.random.randint(key, (B, L), 3, cfg.vocab)}
    if cfg.vlm is not None:
        batch["img_embeds"] = jax.random.normal(
            key, (B, cfg.vlm.n_image_tokens, cfg.vlm.d_image))
    if cfg.encdec is not None:
        if cfg.encdec.frontend == "stub":
            batch["frames"] = jax.random.normal(
                key, (B, cfg.encdec.encoder_seq, cfg.d_model))
        else:
            batch["enc_tokens"] = jax.random.randint(key, (B, 32), 3, cfg.vocab)
    return batch


def train_batch(cfg, key, B=2, L=32):
    b = make_batch(cfg, key, B, L)
    b["labels"] = jnp.roll(b["tokens"], -1, axis=1)
    b["loss_mask"] = jnp.ones((B, L), jnp.float32)
    return b
