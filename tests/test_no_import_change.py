"""No-import-change interposer e2e (reference
python/tests_no_import_change/test_no_import_change.py:18-36: a script importing only
pyspark.ml run under the runner must produce accelerated model types)."""

import os
import subprocess
import sys


SCRIPT = """
import numpy as np, pandas as pd
from pyspark.ml.feature import PCA
from pyspark.ml.clustering import KMeans
from pyspark.ml.tuning import CrossValidator

X = np.random.default_rng(0).normal(size=(100, 6)).astype(np.float32)
df = pd.DataFrame({"features": list(X)})
model = PCA(k=2, inputCol="features").fit(df)
assert type(model).__module__.startswith("spark_rapids_ml_tpu"), type(model)
km = KMeans(k=2, seed=1).fit(df)
assert type(km).__module__.startswith("spark_rapids_ml_tpu"), type(km)
print("NO_IMPORT_CHANGE_OK", type(model).__name__, type(km).__name__)
"""


def test_no_import_change_runner(tmp_path):
    script = tmp_path / "user_script.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_ml_tpu", str(script)],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "NO_IMPORT_CHANGE_OK PCAModel KMeansModel" in out.stdout


def test_install_import_direct():
    """Importing install in-process interposes pyspark.ml.* modules."""
    import sys as _sys

    import spark_rapids_ml_tpu.install  # noqa: hygiene/unused-import

    mod = _sys.modules["pyspark.ml.feature"]
    cls = mod.PCA
    assert cls.__module__.startswith("spark_rapids_ml_tpu")
    # internal callers are not intercepted: the accelerated class itself resolved
    from spark_rapids_ml_tpu.feature import PCA as direct

    assert cls is direct


def test_interposer_tuning_and_assembler():
    """ParamGridBuilder/TrainValidationSplit/VectorAssembler resolve through the
    pyspark.ml proxies (standalone mode)."""
    import subprocess
    import sys

    code = (
        "import spark_rapids_ml_tpu.install\n"
        "from pyspark.ml.tuning import ParamGridBuilder, TrainValidationSplit\n"
        "from pyspark.ml.feature import VectorAssembler\n"
        "import spark_rapids_ml_tpu.tuning as t\n"
        "assert ParamGridBuilder is t.ParamGridBuilder\n"
        "assert TrainValidationSplit is t.TrainValidationSplit\n"
        "print('INTERPOSER_OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        timeout=240,
    )
    assert "INTERPOSER_OK" in out.stdout, out.stdout + out.stderr
