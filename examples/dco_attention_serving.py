"""Beyond-paper: DCO-screened attention for long-context decode.

Applies the paper's two-stage dimension screening to KV-cache retrieval:
stage 1 scores all cached keys on the leading d1 PCA dims, stage 2 runs
exact attention over the top-C survivors.  Compares bytes-touched and
agreement vs exact attention across (d1, cap).

  PYTHONPATH=src python examples/dco_attention_serving.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.serving.dco_attention import (dco_decode_attention,
                                         exact_decode_attention,
                                         fit_key_rotation)
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    B, S, Hkv, G, hd = 4, 4096, 4, 4, 64
    H = Hkv * G
    spec = (np.arange(1, hd + 1) ** -0.8).astype(np.float32)  # key spectrum
    k = (rng.standard_normal((B, S, Hkv, hd)) * spec).astype(np.float32)
    v = rng.standard_normal((B, S, Hkv, hd)).astype(np.float32)
    q = (rng.standard_normal((B, H, hd)) * spec).astype(np.float32)
    rot = jnp.asarray(fit_key_rotation(k.reshape(-1, hd)[:8192]))
    k_rot = jnp.einsum("bshd,de->bshe", jnp.asarray(k), rot)

    exact = np.asarray(exact_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                              jnp.asarray(v), S))
    full_bytes = S * hd * 4
    print(f"cache: S={S} hd={hd}  exact bytes/step/head = {full_bytes/1e6:.2f} MB")
    for d1, cap in [(8, 256), (16, 256), (16, 1024), (32, 1024)]:
        out = np.asarray(dco_decode_attention(jnp.asarray(q), k_rot,
                                              jnp.asarray(v), rot, S,
                                              d1=d1, cap=cap))
        err = np.abs(out - exact).max()
        cos = float((out * exact).sum()
                    / max(np.linalg.norm(out) * np.linalg.norm(exact), 1e-9))
        bytes_ = (S * d1 + cap * hd * 2) * 4
        print(f"d1={d1:3d} cap={cap:5d}  bytes={bytes_/1e6:5.2f} MB "
              f"({bytes_/full_bytes:5.1%})  max_err={err:.4f}  cos={cos:.3f}")


if __name__ == "__main__":
    main()
