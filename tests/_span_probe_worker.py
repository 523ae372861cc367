"""A process-pool worker for tests/test_stage_spans.py, kept in a module that
imports no jax so that the worker process starts without it."""

import sys


class SpanProbeWorker(object):
    """Records spans in a pool worker process and publishes whether jax was
    imported before and after."""

    def __init__(self, worker_id, publish_func, args):
        self.publish_func = publish_func

    def process(self, value):
        from petastorm_tpu.telemetry.spans import (drain_stage_times,
                                                   record_stage, stage_span)
        before = 'jax' in sys.modules
        with stage_span('decode'):
            pass
        span = stage_span('transform').begin()
        span.end()
        record_stage('rowgroup_read', 0.001)
        recorded = sorted(drain_stage_times() or {})
        self.publish_func({'before': before, 'after': 'jax' in sys.modules,
                           'recorded': recorded})

    def shutdown(self):
        pass
