"""Operations and bytes from shapes, for roofline and MFU metrics."""
