"""Framework error types (reference: petastorm/errors.py:16-17, petastorm/utils.py:50-51,
petastorm/etl/dataset_metadata.py PetastormMetadataError).

The resilience subsystem (petastorm_tpu/resilience.py, docs/robustness.md) splits
failures into two classes: *transient* (retryable — network hiccups, throttled object
stores, flaky links) and *permanent* (corrupt data, schema bugs). ``TransientIOError``
marks the former explicitly; ``QuarantinedRowGroupError`` reports a rowgroup that was
skipped under ``on_error='skip'`` and landed in the quarantine ledger.

Strict-typed (mypy.ini ``[mypy-petastorm_tpu.errors]``): the hierarchy is the
machine-readable contract the retry classifier, ledger and doctor key on, so
its structured attributes carry full signatures.
"""

from __future__ import annotations

from typing import Optional


class PetastormTpuError(Exception):
    """Base class for all framework errors."""


class NoDataAvailableError(PetastormTpuError):
    """Raised when a shard (or predicate-filtered view) of the dataset contains no rowgroups
    (reference: petastorm/reader.py:580-582)."""


class DecodeFieldError(PetastormTpuError):
    """Raised when a codec fails to decode a field value (reference:
    petastorm/utils.py:50-51).

    Structured attributes (machine-readable, not just message text):

    - ``field_name``: the Unischema field that failed to decode (None if unknown).
    - ``fragment_path``: the Parquet fragment being read when the decode failed
      (None when decoding outside a rowgroup read, e.g. ``decode_row``).
    """

    def __init__(self, message: str, field_name: Optional[str] = None,
                 fragment_path: Optional[str] = None) -> None:
        super().__init__(message)
        self.field_name = field_name
        self.fragment_path = fragment_path


class MetadataError(PetastormTpuError):
    """Raised when dataset metadata (schema / rowgroup index) is missing or unreadable
    (reference: petastorm/etl/dataset_metadata.py:30-33)."""


class TransientIOError(PetastormTpuError, OSError):
    """An IO failure that is expected to succeed on retry (connection reset, throttled
    object store, wedged link). Subclasses ``OSError`` so generic IO-error handling
    (and the default transient classifier in :mod:`petastorm_tpu.resilience`) treats it
    uniformly with errno-style failures; raise it from custom filesystems to opt an
    error into the retry path explicitly."""


class CacheCorruptionError(PetastormTpuError):
    """A disk-cache entry failed its integrity check (missing/old footer, length
    mismatch, CRC mismatch — ``petastorm_tpu.cache.ArrowIpcDiskCache``). Never
    propagates out of the cache: ``get`` self-heals by deleting the entry and
    serving the fill function (counted in ``stats['corrupt_entries']``); this
    type exists so the self-heal path can be precise about what it catches."""


class WorkerHangError(PetastormTpuError):
    """A pool worker held an item past ``item_deadline_s`` without producing a
    result and was reaped by the watchdog (docs/robustness.md). Under
    ``on_error='skip'`` the item is quarantined with ``reason='hang'`` rather
    than raised; this type names the failure in ledger entries and anywhere a
    strict consumer converts them back into exceptions."""


class QuarantinedRowGroupError(PetastormTpuError):
    """A rowgroup exhausted its error budget under ``on_error='skip'`` and was excluded
    from the stream. Not raised on the hot path (skip mode degrades silently-but-visibly
    through the quarantine ledger); raised by APIs that convert ledger entries back into
    exceptions (e.g. strict post-epoch validation).

    Structured attributes: ``piece_index``, ``fragment_path``, ``row_group_id``,
    ``attempts``, and ``cause`` (the final underlying exception, if available)."""

    def __init__(self, message: str, piece_index: Optional[int] = None,
                 fragment_path: Optional[str] = None,
                 row_group_id: Optional[int] = None,
                 attempts: Optional[int] = None,
                 cause: Optional[BaseException] = None) -> None:
        super().__init__(message)
        self.piece_index = piece_index
        self.fragment_path = fragment_path
        self.row_group_id = row_group_id
        self.attempts = attempts
        self.cause = cause
