"""Misc utilities (reference: petastorm/utils.py:30-47 run_in_subprocess)."""

import pickle


def _subprocess_entry(serialized, result_queue):
    import dill
    try:
        func, args, kwargs = dill.loads(serialized)
        result_queue.put(('ok', pickle.dumps(func(*args, **kwargs))))
    except Exception as exc:  # noqa: BLE001 - every failure must ship to the parent via the queue, not kill the child silently
        import traceback
        result_queue.put(('error', pickle.dumps((exc, traceback.format_exc()))))


def run_in_subprocess(func, *args, **kwargs):
    """Run ``func(*args, **kwargs)`` in a freshly spawned interpreter and return its
    result (reference: petastorm/utils.py:30-47; spawn avoids fork-related breakage of
    JVM / accelerator runtimes)."""
    import multiprocessing as mp

    import dill
    ctx = mp.get_context('spawn')
    result_queue = ctx.Queue()
    serialized = dill.dumps((func, args, kwargs))
    process = ctx.Process(target=_subprocess_entry, args=(serialized, result_queue))
    process.start()
    try:
        # Poll so a child that dies without replying (OOM-kill, segfault, import crash
        # during spawn) surfaces immediately instead of a 10-minute queue.Empty.
        import queue as queue_mod
        import time
        deadline = time.monotonic() + 600
        while True:
            try:
                status, payload = result_queue.get(timeout=1)
                break
            except queue_mod.Empty:
                if not process.is_alive():
                    raise RuntimeError(
                        'Subprocess died with exit code {} before returning a result'
                        .format(process.exitcode)) from None
                if time.monotonic() > deadline:
                    raise TimeoutError('Subprocess produced no result within 600s')
    finally:
        process.join(timeout=30)
        if process.is_alive():
            process.kill()
    if status == 'error':
        exc, tb = pickle.loads(payload)
        raise RuntimeError('Subprocess failed:\n{}'.format(tb)) from exc
    return pickle.loads(payload)
