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
   <stem>, "generation": <stem>}`` naming its modules (a role left out
   takes the default module of that name: the dense family's, and the
   generation of one next token a sequence).
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
   generation what ONE STEP of the family yields, so both comparisons
              of a serving run with the reference go through it (PR 52).
              ``program_rows(model, eng_cfg, ids, seed)`` -> ``{name:
              float32 rows}``: the PROGRAM's logits of the runner's
              seeded sequence through a new cache of the kind, type and
              length the cell's engine holds. The one role that may call
              the program (``model.family``); the ``reference`` role
              still may not. The runner frees the device after it.
              ``reference_rows(reference, canonical, arch, quant, ids)``
              -> the same names and shapes from ONE full pass of the
              reference. The runner takes ``common.relative_l2`` name by
              name against ``reference.tolerance``. A name only one side
              has, a name the two sides shape differently, or fewer rows
              over all names than ``later + 1`` (one of the prefill, one
              for every id that goes through the cache after the prompt)
              makes the run not ``correct``, and standard error says
              which (``serve_runner.rows_fault``). ``ids`` are 32 of
              prompt and 8 ``later`` unless the module states ``SEQUENCE
              = (prompt, later)``, which may lengthen either and shorten
              neither: a family that prefills whole blocks and steps a
              whole block takes multiples of its block, and pays the
              longer pass out of the budget below.
              ``served_gaps(reference, canonical, arch, quant, sample,
              padded)`` -> ``{"first": [...], "later": [...]}``: a gap
              for EVERY served token of ``sample`` (``prompt``,
              ``tokens``, ``steps``), in standard deviations of the
              reference's logits at its position (``served.py``), the
              tokens a prefill gave apart from the later ones;
              ``served.compare`` takes the maxima and the mean and holds
              them to ``reference.served_gap_limits``. It calls the
              reference at the ONE length ``padded``, and no compared
              position may see the padding (a module whose rows see
              their whole block pads from a block's edge).
              ``sample["steps"]`` says which step of the program
              committed each token, where the program's stream events
              carry ``choices[0]["steps"]`` (``loadgen.py``; None where
              they do not): the harness carries the numbers, the module
              and its family give them their meaning.
              The default, ``generation.py``, is one next token a
              sequence: a prefill of 32 ids, then 8 forced one at a time
              through the cache, against one causal pass; a served
              token's gap at the row before it, its context the served
              prefix. A family whose step commits SEVERAL tokens of a
              block, in an order its confidences choose, writes: a
              ``program_rows`` that prefills whole blocks and then runs
              block steps through the cache on a block that holds MASK
              ids at seeded positions; a ``reference_rows`` that is one
              pass over the same ids under the family's mask; a
              ``served_gaps`` that replays each served block step by
              step from ``steps`` (the tokens of steps before ``s`` in
              place, MASK elsewhere) and reads the gap of the tokens
              committed at ``s``. No program sends ``steps`` yet and no
              module replays a block: that half of the seam is carried
              and tested, not proven, until such a program exists
              (PERF.md 7, 35).
3. ``layer_metrics/<metric>.json`` (a reducer of ``layer_metrics`` and
   its arguments) or ``<metric>.py`` (``LAYER``, ``SOURCE``, ``UNIT``,
   ``MOVES`` and ``read(obs)``; None where there is nothing to read),
   ``trace_groups/<group>.json`` for its kernels' names, a traffic
   file where none fits, and the entries in ``BENCHMARK.json``.
``tests/benchmark/test_bench_run_tiny.py`` adds such a configuration
to a copy of the tree and runs it; ``test_bench_generation.py`` adds one
with a ``generation`` module of its own.

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
