"""What an architecture brings to the benchmark, one module per family.

A configuration file states its published ``model_type``; the harness loads
``bench/arch/<model_type>.py`` by path (``bench.manifest.arch``). Adding an
architecture is adding that module; nothing outside this directory changes.
The module defines:

``dims_of(spec) -> dims``
    The sizes of a configuration as run: a frozen, hashable object with at
    least ``layers``, ``vocab`` and ``dtype``, and ``shape(name)`` for each
    tensor drawn through ``bench/weights.py``.
``model_config(name, spec) -> ModelConfig``
    The program's config; raises ``bench.model.ConfigError`` for anything
    the configuration states that the program does not run.
``program_params(model, dims, seed) -> params``
    The seeded weights (``bench/weights.py``) as the program's parameter
    tree, made on the device in one jitted call, in the served dtype. The
    harness checks the tree against the program's schema and serves it on
    the deployment path (``bench/model.py``); the family chooses no engine
    option.
``score(spec, seed, seqs, *, control=False) -> rows``
    The plain reference in float32 at ``highest`` and, with ``control``,
    its int8 control, over whole token sequences, one row per sequence:
    ``best``, ``next`` and with ``control`` ``control``, each over positions
    0..len-2 (``bench/reference.py`` drives the layers). It imports nothing
    of the program.
``model_flops(dims, lanes) -> int``, ``paged_attention_work(dims, lanes)``
    The useful work of one engine step, the latter as ``(flops, bytes)``
    (``bench/work.py`` holds the loop over lanes), read by ``mfu.*`` and
    ``paged_attention_roofline.*``.
``small(spec) -> spec``
    The configuration cut to a size the CPU tests run (``bench/tests``).

and may define ``paper_accounting(name, spec)``: pairs of the family's
weight counts and the program's own accounting, which
``bench/tests/test_work.py`` holds equal.
"""
