"""What the per-model test files build their toy engines from: the one toy
engine configuration, and a module's engines kept by configuration, so that a
test pays for an engine only when its file has not compiled that
configuration yet. A CPU test's time is tracing and compiling (this image has
no usable persistent compile cache: tests/conftest.py), and programs belong to
the runner that traced them; serving the tokens is free.

A test reaches it as the module-scoped fixture `kit` (tests/conftest.py):
`--dist loadfile` keeps a file on one worker, so a module bounds an engine's
life. `kit.engine(config)` is the module's engine of exactly that
configuration, checked DRAINED on every checkout; `kit.fresh_engine(config)`
is an engine nobody else has touched, for the test whose point is that two
ENGINES agree (a fresh one, a roomy and a tight pool, the serial loop). Both
hand over the parameters the module's first engine of that (model, seed)
drew, and the first reuse draws its own and is held to the same bytes.

What may NOT share, and builds with `fresh_engine`: a test that traces under
a tap (llama.ROUTING_TAP, STATE_TAP, BD_TAP, SELECTION_TAP) or under a `monkeypatch`ed
constant or function (llama._ALL_EXPERTS_MAX_TOKENS,
model_runner._BD_COMMIT_PASS, a faulty `bd_transfer`): a shared engine traces
its programs lazily, and one traced there would carry the edit to every later
user (`engine` refuses a set tap and the two constants; a patched function it
cannot see). So does a test that counts compilations or reads what HAS
compiled, one that needs the start-up log, and one that swaps an allocator."""

import dataclasses
import functools
import os
import weakref

import jax
import numpy as np

from vllm_production_stack_tpu.engine import model_runner
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, SchedulerConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.testing.serial_loop import serial_step


def engine_config(model, backend="xla", *, seqs=4, chunk=32, num_blocks=128,
                  decode_window=4, cache=None, sched=None, **kw):
    """The toy engine: pages of 8 tokens, ONE decode and ONE prefill bucket
    (every further bucket is a further program an engine compiles; `sched`
    states them where an assertion reads them), no prefix caching (window
    groups, state slots and diffusion blocks exclude it; `cache` states
    another pool), seed 5."""
    return EngineConfig(
        model=model,
        cache=cache or CacheConfig(block_size=8, num_blocks=num_blocks,
                                   enable_prefix_caching=False),
        scheduler=SchedulerConfig(**{
            "max_num_seqs": seqs, "max_num_batched_tokens": chunk,
            "decode_buckets": (seqs,), "prefill_buckets": (chunk,),
            "decode_window": decode_window, **(sched or {})}),
        attention_backend=backend, prefill_attention_backend=backend, seed=5,
        **kw)


def cut(model, n):
    """The first n layers of a model with a stated per-layer list."""
    return dataclasses.replace(model, num_layers=n, layers=model.layers[:n])


def prompts(model, lengths, seed=1):
    """Random prompts; never the mask token of a model that generates by
    diffusion (a prompt may hold it, but the reference tells a mask by id)."""
    bd = model.block_diffusion
    high = bd.mask_token_id if bd else model.vocab_size
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, high, n))) for n in lengths]


def greedy(max_tokens, **kw):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True, **kw)


def generate(engine, prompts, max_tokens=10):
    return [o["token_ids"] for o in engine.generate(prompts, greedy(max_tokens))]


def squeeze(pool):
    """Take every free page from under the seated rows, as a draft model's
    scratch pages would (give them back with `pool.free_scratch`): admission
    reserves a row's whole budget (`Scheduler._can_admit`), so a pool that
    shrinks under seated rows is the one way left to a preemption by
    growth."""
    held = []
    while (blk := pool.allocate_scratch()) is not None:
        held.append(blk)
    return held


def generate_squeezed(engine, prompts, max_tokens=10):
    """`generate`, with the pool squeezed once all the rows decode: their
    growth finds no page and the newest is preempted; the pages come back at
    the first preemption."""
    sched = engine.scheduler
    ids = [engine.add_request(prompt_token_ids=p, sampling=greedy(max_tokens))
           for p in prompts]
    got = {i: [] for i in ids}
    held, squeezed, before = [], False, sched.total_preemptions
    while engine.has_unfinished():
        if not squeezed and len(sched.running) == len(ids) and all(
                r.output_token_ids for r in sched.running):
            held, squeezed = squeeze(sched.pool), True
        for out in engine.step():
            got[out.request_id].extend(out.new_token_ids)
        if held and sched.total_preemptions > before:
            while held:
                sched.pool.free_scratch(held.pop())
    assert squeezed and not held
    return [got[i] for i in ids]


def finish(engine, *request_ids):
    """Abort what a test would leave running, and step until drained."""
    for rid in request_ids:
        engine.abort_request(rid)
    while engine.has_unfinished():
        engine.step()


def since(engine, before):
    """What `engine` counted after the snapshot `before` (`kit.engine`'s)."""
    return {k: v - before.get(k, 0)
            for k, v in engine.flightrec.timing().items()}


def undrained(engine) -> str:
    """What a user left behind in `engine`, in words; '' when drained."""
    sched = engine.scheduler
    left = []
    if sched.has_unfinished():
        left.append(f"{len(sched.waiting)} requests waiting, "
                    f"{len(sched.running)} running")
    if engine._inflight is not None:
        left.append("a step in flight")
    pool = sched.pool
    for name, group in (("full", pool), ("window", pool.window),
                        ("state", pool.state)):
        if group is not None and group.num_free != group.num_usable:
            left.append(f"{group.num_usable - group.num_free} of the {name} "
                        "group's pages or slots held")
    return "; ".join(left)


# what a program is traced under, as imported (see the docstring)
_TRACED_UNDER = {(mod, name): getattr(mod, name) for mod, name in (
    (llama, "_ALL_EXPERTS_MAX_TOKENS"), (model_runner, "_BD_COMMIT_PASS"),
    (llama, "ROUTING_TAP"), (llama, "STATE_TAP"), (llama, "BD_TAP"),
    (llama, "ROUTER_TAP"), (llama, "SELECTION_TAP"))}


class EngineKit:
    def __init__(self):
        self._params = {}  # (model, seed) -> the tree its first engine drew
        self._drew_again = False  # the first reuse has been checked
        self._engines = {}  # (config, serial) -> [engine, its last user]
        self._built = weakref.WeakSet()  # (a test's own engine goes with it)

    def fresh_engine(self, config, serial=False):
        """An engine of its own (`serial`: testing/serial_loop.py's step)."""
        key = (config.model, config.seed)
        held = None if config.model.checkpoint else self._params.get(key)
        check = held is not None and not self._drew_again
        engine = LLMEngine(config, params=None if check else held)
        self._built.add(engine)
        if check:  # once a module: the handed tree IS the seed's draw
            drawn, handed = (jax.tree.flatten(t) for t in (
                engine.runner.params, held))
            assert drawn[1] == handed[1]
            for a, b in zip(drawn[0], handed[0]):
                assert a.dtype == b.dtype and a.shape == b.shape
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
            self._drew_again = True
        self._params.setdefault(key, engine.runner.params)
        if serial:
            engine.step = functools.partial(serial_step, engine)
        return engine

    def engine(self, config, serial=False):
        """(the module's engine of exactly `config`, its `timing()` now): a
        test asserts on what ITS requests counted (`since`)."""
        for (mod, name), was in _TRACED_UNDER.items():
            assert getattr(mod, name) is was, (
                f"{mod.__name__}.{name} is patched or set: a program traced "
                "now would carry it to the module's later tests — build "
                "this test's engine with kit.fresh_engine")
        me = os.environ.get("PYTEST_CURRENT_TEST", "?").split(" ")[0]
        slot = self._engines.get((config, serial))
        if slot is None:
            slot = self._engines[config, serial] = [
                self.fresh_engine(config, serial), me]
        left = undrained(slot[0])
        assert not left, (
            f"{slot[1]} left the module's engine of this configuration "
            f"undrained ({left}): finish or abort what a test adds")
        slot[1] = me
        return slot[0], slot[0].flightrec.timing()

    def close(self):
        for engine in self._built:
            engine.runner.shutdown()
