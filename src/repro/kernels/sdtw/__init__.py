from .ops import interpret_mode, pallas_carry_init, resolve_blocks, sdtw_pallas

__all__ = ["interpret_mode", "pallas_carry_init", "resolve_blocks",
           "sdtw_pallas"]
