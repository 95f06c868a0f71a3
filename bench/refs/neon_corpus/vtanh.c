/* XNNPACK-style f32 tanh contraction: [3/3] Pade approximant in x^2
 * (Lambert continued fraction truncation), evaluated as two vfma
 * ladders with a vrecpe + 2x vrecps Newton reciprocal — the polynomial
 * microkernel shape whose scalarized baseline is the paper's worst
 * case (Figure 2 vtanh). Input clamped to [-4, 4] (|err| < 7e-4). */
#include <arm_neon.h>

void xnn_f32_vtanh_ukernel(size_t n, const float* x, float* y) {
  const float32x4_t vclamp = vdupq_n_f32(4.0f);
  const float32x4_t vnclamp = vdupq_n_f32(-4.0f);
  const float32x4_t c135135 = vdupq_n_f32(135135.0f);
  const float32x4_t c17325 = vdupq_n_f32(17325.0f);
  const float32x4_t c378 = vdupq_n_f32(378.0f);
  const float32x4_t c62370 = vdupq_n_f32(62370.0f);
  const float32x4_t c3150 = vdupq_n_f32(3150.0f);
  const float32x4_t c28 = vdupq_n_f32(28.0f);
  for (; n >= 4; n -= 4) {
    float32x4_t vx = vld1q_f32(x); x += 4;
    vx = vminq_f32(vmaxq_f32(vx, vnclamp), vclamp);
    float32x4_t vx2 = vmulq_f32(vx, vx);
    /* numerator: x * (((x2 + 378) x2 + 17325) x2 + 135135) */
    float32x4_t vp = vaddq_f32(vx2, c378);
    vp = vfmaq_f32(c17325, vp, vx2);
    vp = vfmaq_f32(c135135, vp, vx2);
    vp = vmulq_f32(vp, vx);
    /* denominator: ((28 x2 + 3150) x2 + 62370) x2 + 135135 */
    float32x4_t vq = vfmaq_f32(c3150, vx2, c28);
    vq = vfmaq_f32(c62370, vq, vx2);
    vq = vfmaq_f32(c135135, vq, vx2);
    /* reciprocal: vrecpe seed + two vrecps Newton steps */
    float32x4_t vr = vrecpeq_f32(vq);
    vr = vmulq_f32(vr, vrecpsq_f32(vq, vr));
    vr = vmulq_f32(vr, vrecpsq_f32(vq, vr));
    vst1q_f32(y, vmulq_f32(vp, vr)); y += 4;
  }
}
