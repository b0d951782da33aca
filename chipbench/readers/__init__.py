"""One module per kind of reading.  ``read(ctx, **args)`` returns a number, or
None when there is nothing to read (the harness then leaves the metric out).
``ctx`` is the run's context (run.py ``reader_context``): ``model``, ``serve``,
``peaks``, ``before``/``after`` (parsed /metrics around the window), ``window``
(the generator's report), ``seconds`` and ``trace`` (a DeviceTrace or None).
"""
