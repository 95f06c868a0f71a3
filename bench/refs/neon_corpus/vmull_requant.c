/* Requantizing qs8 multiply — the widening-multiply path the paper's
 * XNNPACK evaluation leans on (vmull -> RVV vwmul.vv, one instruction
 * writing a double-width group; vqmovn -> vnclip):
 *   y[i] = sat8(((int16) a[i] * b[i]) >> 5)
 * The >> 5 keeps the product range wide enough that vqmovn saturates
 * genuinely (|p| reaches 512).                                        */
#include <arm_neon.h>

void qs8_vmul_requant_ukernel(size_t n, const int8_t* a, const int8_t* b,
                              int8_t* y) {
  for (; n >= 8; n -= 8) {
    int8x8_t va = vld1_s8(a); a += 8;
    int8x8_t vb = vld1_s8(b); b += 8;
    int16x8_t vprod = vmull_s8(va, vb);
    vprod = vshrq_n_s16(vprod, 5);
    vst1_s8(y, vqmovn_s16(vprod)); y += 8;
  }
  for (; n != 0; n -= 1) {
    int32_t p = ((int32_t) *a * (int32_t) *b) >> 5;
    a += 1; b += 1;
    p = p > 127 ? 127 : p;
    p = p < -128 ? -128 : p;
    *y = (int8_t) p; y += 1;
  }
}
