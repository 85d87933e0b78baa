"""The harness's own calls into the replica (``facts``, ``trace_start``,
``trace_stop``, ``margins``) run under the harness's deadline; a user's
request that brings no deadline still gets serve's default. CPU, one
stub deployment."""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.jobs import serve as serve_job  # noqa: E402

SERVE_DEFAULT_S = 0.5
NAP_S = 2.5  # past the default and the second of grace a reply gets


@pytest.fixture
def handle():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=2, system_config={
        "num_prestart_workers": 1,
        "serve_default_request_timeout_s": SERVE_DEFAULT_S})

    @serve.deployment(num_replicas=1)
    class Replica:
        """``trace_stop`` takes as long as ``stop_trace()`` of a loaded
        window can: longer than serve lets a user's request wait."""

        def trace_stop(self):
            time.sleep(NAP_S)
            return {"stop_trace_s": NAP_S}

        def __call__(self, _):
            time.sleep(NAP_S)
            return "late"

    try:
        yield serve.run(Replica.bind(), name="replica")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_a_control_call_outlives_the_serve_default_and_a_request_does_not(
        handle):
    from ray_tpu.core.exceptions import DeadlineExceededError

    assert serve_job.CONTROL_TIMEOUT_S == 900.0
    started = time.monotonic()
    assert serve_job.control_call(handle, "trace_stop") == {
        "stop_trace_s": NAP_S}
    assert time.monotonic() - started >= NAP_S
    # The same handle, the same replica, no deadline brought: cut at the
    # default, well before the method returns.
    started = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        handle.remote(None).result(timeout=60)
    assert time.monotonic() - started < NAP_S
    # The parent's call: the method the harness needs, cut the same way.
    with pytest.raises(DeadlineExceededError):
        handle.options(method="trace_stop").remote().result(timeout=900)
