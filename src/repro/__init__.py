"""NERO weather stencils on JAX/Pallas."""

import os


def _host_rounds_once() -> None:
    """Compile host (CPU) code for an instruction set without fused
    multiply-add, unless the caller chose an ISA.

    XLA's CPU backend lets LLVM fuse `a * b + c` into one FMA wherever
    instruction selection finds the pattern, and whether it does depends on
    the vector width and the fusion around it.  Two programs that compute
    the same element in differently shaped arrays — a k-step round and two
    single-step rounds, or the same state on a 2x2 and on a 2x1 mesh — then
    round it differently, and the serving contract (every result bitwise
    equal to a solo run) cannot hold on the CPU.  Without FMA every multiply
    and add rounds once, whatever the shape.  XLA reads the flag when its
    CPU client starts, so this runs before any JAX computation of a process
    that imports the package; the TPU backend ignores it."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_cpu_max_isa" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX").strip()


_host_rounds_once()
