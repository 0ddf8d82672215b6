"""Quickstart: the paper's subject end to end through the facade.

Builds a synthetic embedding corpus, opens one session per DCO method
(one per paper category), and compares QPS / recall / pruning — then A/Bs
the same exact method on the host and JAX backends, which is the whole
point of the unified API.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.api import open_index
from repro.vecdata import load_dataset
from repro.vecdata.synthetic import recall_at_k
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ds = load_dataset("gist", scale=0.2)          # 6k x 960 image embeddings
    print(f"dataset: {ds.name}  N={ds.n}  D={ds.dim}")
    gt, _ = ds.ground_truth(10)

    for name in ("FDScanning", "PDScanning+", "DDCres"):
        sess = open_index(ds.X, index="ivf", method=name,
                          index_params={"n_list": 64})
        res = sess.search(ds.Q[:20], 10, nprobe=16)
        rec = recall_at_k(res.ids, gt[:20])
        print(f"{name:12s}  QPS={res.qps:7.1f}  recall@10={rec:.3f}  "
              f"dims pruned={res.stats.pruning_ratio:.1%}")

    # host vs device is an A/B flag, not a second API
    for backend in ("host", "jax"):
        sess = open_index(ds.X, index="flat", method="PDScanning+",
                          backend=backend)
        sess.search(ds.Q[:20], 10)                # warm up (jit compile)
        res = sess.search(ds.Q[:20], 10)
        rec = recall_at_k(res.ids, gt[:20])
        print(f"flat/{backend:4s}    QPS={res.qps:7.1f}  recall@10={rec:.3f}")


if __name__ == "__main__":
    main()
