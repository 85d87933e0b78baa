"""Fast CPU smoke of the compiled training step (`make check` gate).

One tiny pjit'd step through the full fused path — chunked-scan
schedule, donated params + optimizer state, compiled init — so a
pjit/scan/donation regression fails in CI seconds instead of surfacing
as a broken run on the chip. Mirrors what a train worker does, minus
the cluster (this must stay cheap enough for every `make check`).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main() -> int:
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import LlamaConfig
    from ray_tpu.train.compiled_step import CompiledTrainStep

    cfg = dataclasses.replace(
        LlamaConfig.tiny(), num_layers=2, scan_layers=True, scan_chunk=1
    )
    step = CompiledTrainStep(cfg)
    params, opt_state = step.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 33))
    )
    params, opt_state, loss = step(params, opt_state, tokens)
    loss0 = float(loss)
    assert np.isfinite(loss0), f"smoke loss not finite: {loss0}"
    # Second step reuses the executable (donated buffers really rebind)
    # and must not recompile.
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    stats = step.compile_stats()
    assert stats["executables"] == 1, f"unexpected recompile: {stats}"
    print(
        f"train-smoke OK: loss {loss0:.4f} -> {float(loss):.4f}, "
        f"{stats['executables']} executable(s), "
        f"{time.perf_counter() - t0:.1f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
