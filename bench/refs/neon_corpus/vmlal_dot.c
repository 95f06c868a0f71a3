/* Widening dot-product contraction: int8 inputs accumulate their
 * double-width products into an int16 register via vmlal (RVV
 * vwmacc.vv), one vaddvq horizontal reduction, scalar tail folded
 * into the reduced sum. */
#include <arm_neon.h>

void qs8_vmlal_dot_ukernel(size_t n, const int8_t* a, const int8_t* b,
                           int16_t* sum) {
  int16x8_t vacc = vdupq_n_s16(0);
  for (; n >= 8; n -= 8) {
    int8x8_t va = vld1_s8(a); a += 8;
    int8x8_t vb = vld1_s8(b); b += 8;
    vacc = vmlal_s8(vacc, va, vb);
  }
  int16_t vsum = vaddvq_s16(vacc);
  for (; n != 0; n -= 1) {
    vsum = vsum + *a * *b;
    a += 1; b += 1;
  }
  *sum = vsum;
}
