"""The cross-chip collectives' share of device busy time, in %: every op
whose HLO opcode is one of XLA's collectives (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute, synchronous or as a
start/done pair) over busy.  Matched on the opcode, not the instruction's
name: the trace names an all-reduce that ``jax.lax.psum`` made
``%psum.7 = f32[5000,20]{...} all-reduce(...)``."""

COLLECTIVES = (r"\s(?:all-gather|all-reduce|reduce-scatter|all-to-all"
               r"|collective-permute)(?:-start|-done)?\(",)


def read(ctx):
    s = ctx["trace"]
    if s is None or not s.busy_s:
        return None
    t = s.seconds(COLLECTIVES)
    return None if t is None else 100.0 * t / s.busy_s
