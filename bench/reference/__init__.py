"""Plain float32 references that decide a benchmark run's ``correct``.

Nothing here imports the program under test (``repro``)."""
