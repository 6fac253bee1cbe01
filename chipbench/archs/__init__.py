"""Architectures: one module per kind of block, found by name.

A configuration file may name its architecture, ``"arch": "<name>"``
(absent, it is ``"dense"``); ``spec.load_cell`` then loads
``archs/<name>.py`` and puts it on ``Cell.arch``.  Everything a run needs
to know of the model's shapes and equations comes from that module, so
a new architecture is added as new files (its module, its configuration,
its cells) and no file of the harness changes.

Each module exposes:

``dims(cfgfile)``
    The model's sizes from the file's ``model`` block: a frozen,
    hashable object with at least ``vocab`` and ``layers``.
``make_weights(dims, seed)``
    Every weight on the device from ``seed`` in one jitted call, in the
    layout of the program's parameter tree, as served.
``program_config(cfgfile)``
    ``(ModelConfig, ServeConfig)`` of the program, as the file states.
``logits(params, dims, tokens, bits=None)``
    Next-token logits ``[B, T, V]`` of ``tokens`` ``[B, T]`` by the plain
    reference: float32 ``jax.numpy`` at ``highest`` precision, imports
    nothing of the program; with ``bits``, the ``bits``-bit control.
``model_flops(dims, tokens, context_sum)``
    Model FLOPs of ``tokens`` tokens whose contexts sum to
    ``context_sum``, whatever numeric path runs them (``step_mfu``).
``fused_launches(dims, rows, digits)``
    ``(ops, bytes)`` of each fused residue launch of one packed step of
    ``rows`` lanes (``rns_kernel_roofline``), for instance from
    ``flops.fused_dot_cost``.
"""
