"""Share of the traced decode call's device busy time spent in ops that
read, update, shift, stack or copy a state-space model's recurrent state or
conv state: device self time of the ops whose innermost program scope is
``ssm_state``, over the call's busy time.

The decode step runs its layer scan under ``ssm_state`` and the rest of
each layer under ``ssm`` (``models/ssm.py``, ``models/transformer.py``),
so the scan's slices and stacks of the per-layer state, which sit under the
scan's scope alone, count, and ops under ``ssm``, ``mlp``, ``lm_head`` or
any other of the program's scopes do not.  XLA's own copies carry no name
stack at all: such an op counts where its result has the shape of a
counted op's result of three or more axes (the stacked state that the scan
writes is copied whole into the step's output).  Nothing is read without a
device plane or the program's ``serve.generate`` span."""
from pathlib import Path

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_out" / "trace"
STATE = "ssm_state"


def _innermost(stack: str, names) -> str:
    return next((p for p in reversed(stack.rstrip(":").split("/"))
                 if p in names), "")


def share(t):
    """Percent of the traced call's busy time in the state's ops, on the
    lists ``scopes.load`` gives; None where there is nothing to read."""
    import devtrace
    import scopes
    names = scopes.SCOPES + ("ssm", STATE)
    w, busy = scopes.window(t), scopes.busy_s(t)
    if w is None or not busy or not any(s[0] == scopes.GENERATE
                                        for s in t["spans"]):
        return None
    scope = {n: _innermost(t["scopes"].get(n, ""), names)
             for ops in t["devices"] for n, _, _ in ops}
    shapes = {scopes.result_dims(n) for n, s in scope.items() if s == STATE}
    state = {n for n, s in scope.items() if s == STATE or (
        s == "" and len(scopes.result_dims(n) or ()) >= 3
        and scopes.result_dims(n) in shapes)}
    ns = 0
    for ops in t["devices"]:
        clipped = scopes._clipped(ops, *w)
        # _self_times labels each op by its name; index them to keep names
        for i, own in devtrace._self_times(
                [(str(k),) + iv[1:] for k, iv in enumerate(clipped)]):
            if clipped[int(i)][0] in state:
                ns += own
    return 100.0 * ns / 1e9 / len(t["devices"]) / busy


def read(facts):
    trace = facts.get("trace")
    if facts.get("kind") != "decode" or not trace or not trace["busy_s"]:
        return None
    import scopes
    t = scopes.load_dir(TRACE_DIR)
    return None if t is None else share(t)
