"""The benchmark's harness: everything that is the same for every cell.

Nothing in this package names a cell, a configuration or a model; those
live in data files that ``spec`` resolves by the names in
``BENCHMARK.json``.

Adding a configuration of another architecture (new files and new
entries only; nothing that is here needs an edit):

1. ``configs/<name>.json``: ``source``, ``hf_config`` (published, what
   the program's registry loads), ``quant``, ``quant_block``, ``engine``
   and/or ``train``, ``reference`` (what the reference module reads as
   ``arch``), ``reduced``, ``assumed``, ``deployment``, ``tiny``, and
   ``"harness": {"reference": <stem>, "weights": <stem>, "costs":
   <stem>}`` naming its modules (a role left out takes the dense
   module of that name).
2. ``harness/<stem>.py`` for each role named, held at load to
   ``spec.MODULE_CONTRACT`` (a missing file or function is a
   ``SpecError`` that says which):
   reference  ``all_logits(params, arch, quant, token_ids, first=0)``
              -> float32 ``[S - first, V]``, plain ``jax.numpy``, no
              import of the program;
              ``tolerance(config, kv_cache_dtype)`` -> bound on the
              relative L2 of the program's logits, with its derivation;
              ``served_gap_limits(config, kv_cache_dtype)`` -> limits on
              what ``served.compare`` reads. ``relative_l2``,
              ``next_token_loss``, ``unpack_sym_int4`` are there to import.
   weights    ``build_model(config, seed, merge, with_canonical=None)``
              -> ``(model the engine serves, {stage: seconds})``, made
              on the device in one jitted call;
              ``canonical_params(config, seed)`` -> the tree the
              reference reads, from the seed alone.
   costs      ``Dims.from_config(config)`` (at least
              ``num_hidden_layers``, ``vocab_size``);
              ``kv_bytes_per_token(dims, seq_len, kv_cache_dtype)``;
              ``serving_work(config, dims, records, kv_cache_dtype,
              trace_ab)`` and ``training_work(config, dims, traffic,
              tokens_per_step)`` -> ``obs["work"]``, whose keys the
              roofline readers name.
3. ``layer_metrics/<metric>.json`` (a reducer of ``layer_metrics`` and
   its arguments) or ``<metric>.py`` (``LAYER``, ``SOURCE``, ``UNIT``,
   ``MOVES`` and ``read(obs)``; None where there is nothing to read),
   ``trace_groups/<group>.json`` for its kernels' names, a traffic
   file where none fits, and the entries in ``BENCHMARK.json``.
``tests/benchmark/test_bench_run_tiny.py`` adds such a configuration
to a copy of the tree and runs it.

A new cell's time budget (PR 38; the driver stops a run at 360 s, and a
run that is stopped loses the PR, whoever's it is):

- its whole run, process start to the result line (``wall_s`` on the
  ``"info": "run"`` note line and on the last line of standard error,
  beside ``common.RUN_BUDGET_S``), ends within 270 s warm in the median
  of six seeds and within 300 s in EVERY warm run, traced or not; its PR
  reports the note line's ``phases`` in PERF.md, cold and warm;
- what runs after the window (``check_a_program``, ``canonical_tree``,
  ``layer_check``, ``check_a_reference``, ``check_b_served``) takes at
  most 75 s warm. ``canonical_params`` is called ONCE, and the tree it
  returns serves every comparison. Nothing after the window may
  compile in a warm run: ``served.compare`` pads every request of a
  sample to ONE length (``served.pad_length`` of the longest request
  the traffic can ask for, not of what a run finished), because a
  reference compiles a set of programs per length, a minute each at
  14k tokens;
- a configuration's own layer check (``checks_<family>.layer_check``,
  run inside ``canonical_params``) covers each KIND of layer once, at
  sizes where its mechanisms bind, never every layer of the cut; its
  programs are jitted once per kind; it leaves ``{"seconds", "within",
  "compared": [(name, value, limit[, "floor"])]}`` on the tree under
  ``"layer_check"``, which the runner charges to the ``layer_check``
  phase, prints among the compared numbers and holds ``correct`` to;
- warm-up is the traffic generator's (``traffic.warmup_plan``: one
  prompt for each class of prompt the window holds, then the sampling
  variants), and it is set-up, inside the same budget: in the one
  long-context cell its prompts cost about 15 s of a 67 s warm-up, the
  rest is loading the engine's programs (PERF.md 2, PR 38), so keep a
  cell's buckets few before its prompts.
"""
