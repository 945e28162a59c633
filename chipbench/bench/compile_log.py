"""Backend compiles, counted from JAX's monitoring events (a copy of the
bring-up smoke's ``CompileLog``)."""
from __future__ import annotations

from typing import List, Tuple


class CompileLog:
    """XLA compile seconds per program, from JAX's monitoring events
    (register the instance as an event-duration listener)."""

    def __init__(self) -> None:
        self.events: List[Tuple[str, float]] = []

    def __call__(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), secs))
