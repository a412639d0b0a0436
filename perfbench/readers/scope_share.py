"""Share of device time by source scope: the time of the executed HLO
instructions whose path in the program's scope table has one of ``scopes``
(``jax.named_scope`` names, flax module names, JAX's own
``rematted_computation``) over the time of all executed instructions, or
of those of one ``program`` (``[module, name]`` of a constant the program
exports). With ``unattributed``, the share whose instruction is not in the
table or has no scope below its program's root. Instructions of one core
do not overlap, so their sum is the busy time. The note logs each scope
apart, the costliest instructions with their scope, and the costliest
without one."""
from perfbench import program_spans as ps


def read(ctx, scopes=(), program=None, unattributed=False):
    prog = ps.of(ctx)
    if prog is None or prog.rows is None:
        return None
    if unattributed:
        ctx.notes["scope_top_ops"] = ps.top_rows(prog, 10)
        ctx.notes["scope_top_unattributed"] = ps.top_rows(
            prog, 10, keep=lambda r: not prog.scopes.attributed(r))
        return ps.unattributed_share(prog)
    name = ps.program_constant(*program) if program else None
    if program and not name:
        return None
    found = ps.scope_share(prog, list(scopes), name)
    if found is None:
        return None
    ctx.notes["scope_share:" + "+".join(scopes)] = found[1]
    return found[0]
