"""Out-of-core tier walkthrough: datasets larger than device memory.

The reference leans on CUDA managed memory (UVM/SAM) to fit beyond-GPU-memory
datasets (reference utils.py:184-241). The TPU rebuild replaces paging with
explicit streaming — and it is AUTOMATIC: any estimator whose input exceeds
`stream_threshold_bytes` routes onto its streamed path with identical results.
This example forces the threshold low so the routing is visible at demo sizes.

Run: JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/out_of_core_tier.py
"""

import numpy as np
import pandas as pd

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.clustering import DBSCAN
from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors, NearestNeighbors

rng = np.random.default_rng(0)
n, d = 20_000, 16
centers = rng.normal(0, 10, (4, d)).astype(np.float32)
assign = rng.integers(0, 4, n)
X = (centers[assign] + rng.normal(0, 0.5, (n, d))).astype(np.float32)
df = pd.DataFrame({"features": list(X), "id": np.arange(n)})
df["label"] = (assign % 2).astype(np.float64)

# pretend the data does not fit: everything below streams (watch the log lines)
config.set("stream_threshold_bytes", 64 * 1024)
config.set("stream_batch_rows", 4096)
try:
    # allreduce family: streamed sufficient-statistics accumulation (exact)
    lr = LogisticRegression(regParam=0.01, featuresCol="features").fit(df)
    print("streamed LogReg n_iter:", lr.get_model_attributes()["n_iter"])

    # broadcast-replicate family: host-resident pairwise tiles
    labels = DBSCAN(eps=2.5, min_samples=5).fit(df).transform(df)["prediction"]
    print("streamed DBSCAN clusters:", len(set(labels) - {-1}))

    nn = NearestNeighbors(k=4, inputCol="features", idCol="id").fit(df)
    _, _, knn_df = nn.kneighbors(df.head(8))
    print("streamed exact kNN first row ids:", list(knn_df["indices"][0]))

    # ANN family: streamed IVF build, paged probe search
    ann = ApproximateNearestNeighbors(
        k=4, algorithm="ivfpq", inputCol="features", idCol="id",
        algoParams={"nlist": 32, "nprobe": 8, "M": 4, "n_bits": 6},
    ).fit(df)
    _, _, ann_df = ann.kneighbors(df.head(8))
    print("streamed IVF-PQ first row ids:", list(ann_df["indices"][0]))
finally:
    config.unset("stream_threshold_bytes")
    config.unset("stream_batch_rows")
print("out-of-core tier OK")
