"""Program start-up: the persistent compile cache (``compile_cache``)."""
