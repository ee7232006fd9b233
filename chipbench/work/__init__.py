"""Operations and bytes of one forward, one module per model family."""
