"""Runtime pieces, imported by module (``repro.runtime.spans``,
``repro.runtime.monitor``, ...) so that the KRR solver's spans load nothing
of the LM training loop."""
