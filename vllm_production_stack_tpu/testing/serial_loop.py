"""The serial step loop: the tests' bitwise reference for the engine's
pipelined `LLMEngine.step`.

schedule → dispatch → sync → postprocess, one device step per call and
nothing in flight between calls. It shares `_schedule`, `_dispatch`, `_resolve`
and `_drop_finished` with the pipelined loop, so what it pins down is the
pipeline itself: speculative scheduler state, device-side token chaining
and rollback must leave every stream as this loop emits it. No config
field, flag or HTTP field selects it."""

from __future__ import annotations

import functools
import time

from ..engine.config import EngineConfig
from ..engine.engine import LLMEngine
from ..engine.request import RequestOutput
from ..engine.scheduler import PrefillWork


def serial_step(engine: LLMEngine) -> list[RequestOutput]:
    outputs: list[RequestOutput] = []
    with engine.flightrec.wall("step_wall_s"):
        work = engine._schedule(outputs)
        if work is not None:
            # dispatch and resolve at once: here the host's wait per
            # dispatch (prefill_s / decode_s) takes in the dispatch too
            key = "prefill_s" if isinstance(work, PrefillWork) else "decode_s"
            with engine.flightrec.wall(key):
                handle = engine._dispatch(work)
            engine._resolve(work, handle, outputs)
        elif engine.scheduler.hydration_parked():
            time.sleep(0.001)  # see the pipelined loop's parked note
        with engine.flightrec.span("post"):
            engine._drop_finished(outputs)
    return outputs


def serial_engine(config: EngineConfig) -> LLMEngine:
    """An engine whose `step` (and so `generate`) is the serial loop."""
    engine = LLMEngine(config)
    engine.step = functools.partial(serial_step, engine)
    return engine
