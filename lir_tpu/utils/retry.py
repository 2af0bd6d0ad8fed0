"""Exponential-backoff retry (reference: perturb_prompts.py:72-106).

Generic over exception types so the same policy covers the optional remote-API
backend, the serve supervisor's device dispatches, and any transient local
failure (e.g. filesystem hiccups on a preemptible host). Default policy
parity: 10 retries, 60 s initial delay capped at 300 s, x1.5 backoff, uniform
0.8-1.2 jitter. Two extensions over the reference (config.RetryConfig):

- ``full_jitter``: AWS-style full jitter (wait ~ U[0, delay]) instead of the
  multiplicative band — decorrelates many clients retrying one contended
  resource.
- ``max_elapsed``: a cap on the TOTAL wall time the retry loop may consume
  (attempts + sleeps). The reference's unbounded loop can exceed any caller
  deadline (10 retries at 300 s is 50 minutes); with the cap, once another
  sleep would cross it the last failure re-raises immediately, so a retried
  call composes with the serving layer's per-request deadlines.

KeyboardInterrupt and SystemExit are NEVER retried, even when a caller
passes a broad ``retry_on`` tuple (``(Exception,)`` is common and
``(BaseException,)`` has appeared in chaos wrappers): Ctrl-C during a
300 s backoff sleep must exit promptly, not be logged as "attempt 3
failed (KeyboardInterrupt)" and slept through seven more times.
"""

from __future__ import annotations

import random
import time
from typing import Callable, Optional, Tuple, Type, TypeVar

from lir_tpu.config import RetryConfig

T = TypeVar("T")


def retry_with_exponential_backoff(
    fn: Callable[[], T],
    retry_on: Tuple[Type[BaseException], ...] = (Exception,),
    config: RetryConfig = RetryConfig(),
    sleep: Callable[[float], None] = time.sleep,
    log: Callable[[str], None] = print,
    clock: Callable[[], float] = time.monotonic,
    give_up: Optional[Callable[[BaseException], bool]] = None,
) -> T:
    """``give_up(exc)`` true re-raises at once, whatever ``retry_on``
    says: a failure that retrying cannot change (faults.is_program_error
    — a program the compiler refuses) is not slept on."""
    delay = config.initial_delay
    start = clock()
    for attempt in range(config.max_retries + 1):
        try:
            return fn()
        except retry_on as exc:
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise  # shutdown signals are not transient failures
            if attempt == config.max_retries or (give_up is not None
                                                 and give_up(exc)):
                raise
            if config.full_jitter:
                wait = random.uniform(0.0, min(delay, config.max_delay))
            else:
                wait = min(delay * random.uniform(*config.jitter),
                           config.max_delay)
            if (config.max_elapsed is not None
                    and clock() - start + wait > config.max_elapsed):
                log(
                    f"Attempt {attempt + 1}/{config.max_retries + 1} failed "
                    f"({type(exc).__name__}: {exc}); next retry would exceed "
                    f"the {config.max_elapsed:.1f}s elapsed cap — giving up"
                )
                raise
            log(
                f"Attempt {attempt + 1}/{config.max_retries + 1} failed "
                f"({type(exc).__name__}: {exc}); retrying in {wait:.1f}s"
            )
            sleep(wait)
            delay = min(delay * config.backoff_factor, config.max_delay)
    raise AssertionError("unreachable")
