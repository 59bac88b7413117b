"""The program's host spans and device scopes (``repro.telemetry.spans``):
every span opens where its table says, in the drivers' order and
nesting; every scope lands on the chunk programs' ``op_name`` metadata,
one per op at most; and neither changes a result."""
import contextlib
import dataclasses
import re

import jax
import pytest

from repro import telemetry as tl
from repro.configs import get_arch
from repro.telemetry import spans

T0, MAX_ROUNDS, CHUNK = 2, 2, 2

#: one chunk of each stage: (name, children) in the order they open
CHUNK_LOOP = [("driver.dispatch", []), ("driver.sync", []),
              ("telemetry.fetch", []), ("telemetry.price", [])]
PROCESS = ("driver.process", [
    ("driver.meta_train", CHUNK_LOOP),
    *[("driver.adapt_task", [("driver.setup", []), *CHUNK_LOOP,
                             ("driver.bill", [])])] * 6])


def _case_study(**kw):
    from repro.rl.casestudy import CaseStudy
    cfg = dataclasses.replace(get_arch("paper-dqn"), num_layers=2,
                              d_model=32)
    return CaseStudy(cfg=cfg, chunk=CHUNK, **kw)


@pytest.fixture(scope="module")
def recorded():
    """A tiny whole process with buffered telemetry, its spans recorded
    as a tree of (name, args, children)."""
    root = ("", {}, [])
    stack = [root]

    @contextlib.contextmanager
    def fake(name, **args):
        assert spans.PREFIX + name in spans.SPANS
        node = (name, args, [])
        stack[-1][2].append(node)
        stack.append(node)
        try:
            yield
        finally:
            stack.pop()

    mp = pytest.MonkeyPatch()
    mp.setattr(spans, "span", fake)
    try:
        cs = _case_study(telemetry=tl.Telemetry())
        res = cs.run(jax.random.PRNGKey(3), T0, max_rounds=MAX_ROUNDS)
    finally:
        mp.undo()
    return cs, res, root[2]


def _shape(nodes):
    return [(name, _shape(kids)) for name, _, kids in nodes]


def _shape_of(spec):
    return [(name, _shape_of(kids)) for name, kids in spec]


def test_process_opens_every_span_in_order_and_nesting(recorded):
    _, _, tree = recorded
    assert _shape(tree) == [(PROCESS[0], _shape_of(PROCESS[1]))]
    seen = set()

    def walk(nodes):
        for name, _, kids in nodes:
            seen.add(spans.PREFIX + name)
            walk(kids)

    walk(tree)
    assert seen == set(spans.SPANS)


def test_adapt_task_spans_carry_the_task(recorded):
    _, _, tree = recorded
    tasks = [args for name, args, _ in tree[0][2]
             if name == "driver.adapt_task"]
    assert tasks == [{"task_id": t} for t in range(6)]


def test_every_ledger_fetch_makes_one_copy(recorded):
    """Meta and FL chunks alike: each ``telemetry.fetch`` span says its
    chunk's rows reached the host in one device→host copy."""
    _, _, tree = recorded
    fetches = []

    def walk(nodes):
        for name, args, kids in nodes:
            if name == "telemetry.fetch":
                fetches.append(args)
            walk(kids)

    walk(tree)
    assert len(fetches) >= 7
    assert all(args == {"copies": 1} for args in fetches), fetches


def test_spans_leave_results_bit_identical(recorded):
    """Real spans with telemetry off give the recorded run's meta
    losses, t_i and rewards, bit for bit."""
    _, res, _ = recorded
    again = _case_study().run(jax.random.PRNGKey(3), T0,
                              max_rounds=MAX_ROUNDS)
    assert again.meta_history == res.meta_history
    assert again.rounds_per_task == res.rounds_per_task
    assert again.fl_histories == res.fl_histories


def test_unknown_span_is_refused():
    with pytest.raises(ValueError, match="unknown span"):
        spans.span("driver.nope")


def _scopes_of(op_name):
    return [s for s in spans.SCOPES
            if re.search(rf"(^|[/(]){s}([)/]|$)", op_name)]


def test_chunk_programs_carry_every_scope_one_per_op(recorded):
    cs, _, _ = recorded
    ops = []
    for prog in (cs._meta_chunk, cs._fl_chunks[0]):
        rec = prog._program_record
        hlo = rec.jitted.lower(*rec.abstract_args).as_text(
            dialect="hlo", debug_info=True)
        ops += re.findall(r'op_name="([^"]*)"', hlo)
    assert ops
    found = set()
    for op in ops:
        hit = _scopes_of(op)
        assert len(hit) <= 1, op
        found.update(hit)
    assert found == set(spans.SCOPES)


def test_scope_match_reads_transformed_names():
    assert _scopes_of("jit(f)/while/body/episodes/dot_general") \
        == ["episodes"]
    assert _scopes_of("jit(f)/transpose(jvp(maml_step))/mul") \
        == ["maml_step"]
    assert _scopes_of("jit(f)/vmap(local_sgd)/sub") == ["local_sgd"]
    assert _scopes_of("jit(f)/my_episodes_x/add") == []
