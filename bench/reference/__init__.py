"""The benchmark's plain reference: the array simulator's semantics,
frozen when the benchmark was defined, on plain jnp ops.

These modules are copies of ``repro.core.pages`` and
``repro.core.array_sim`` (``spec``, ``compiler``, ``coop``, ``policies``,
``sim``) and of ``repro.obs.counters``, with imports made local and the
substrate ops passed in as ``kernels`` (default :class:`.kernels.Oracles`).  The
three substrate ops are the plain forms in :mod:`.kernels`, never the
program's Pallas kernels.  Nothing here imports the program: a later
change to the program cannot move what its results are compared with.
"""
